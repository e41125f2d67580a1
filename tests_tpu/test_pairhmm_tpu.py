"""Real-TPU certification of the batched PairHMM forward kernel.

The main suite pins the anti-diagonal scan's tolerance parity with the
scalar float64 golden on CPU (tests/test_pairhmm.py), which proves the
formulation but not that XLA's TPU lowering of the scan (f32 logaddexp
chains, dynamic slices, masked selects) holds the same contract on
hardware — the exact gap the scatter-kernel leg exists for. This leg
runs only with a live TPU backend (skips cleanly anywhere else, the
tests_tpu/ discipline): the COMPILED forward pass must match the
float64 golden within the documented tolerances, and a compiled tile
must be bit-identical to itself under batch permutation on the chip.

jax imports stay inside fixtures/bodies — collection must never
initialize a backend (tests_tpu/conftest.py).
"""

import numpy as np
import pytest


def _pairs(rng, shapes):
    out = []
    for rl, hl in shapes:
        hap = rng.integers(0, 4, hl).astype(np.int8)
        off = int(rng.integers(0, max(1, hl - rl)))
        read = hap[off : off + rl].copy()
        errs = rng.random(read.size) < 0.05
        read[errs] = rng.integers(0, 4, int(errs.sum()))
        out.append(
            (read, rng.integers(5, 55, read.size).astype(np.int32), hap)
        )
    return out


def _batch(pairs, r_b, h_b):
    b = len(pairs)
    rc = np.zeros((b, r_b), np.int8)
    rq = np.zeros((b, r_b), np.int32)
    hc = np.full((b, h_b), 4, np.int8)
    rl = np.zeros(b, np.int32)
    hl = np.zeros(b, np.int32)
    for k, (read, quals, hap) in enumerate(pairs):
        rc[k, : read.size] = read
        rq[k, : quals.size] = quals
        hc[k, : hap.size] = hap
        rl[k] = read.size
        hl[k] = hap.size
    return rc, rq, rl, hc, hl


class TestPairHmmForwardOnHardware:
    def test_compiled_forward_holds_golden_parity(self, tpu):
        """The hardware parity pin: mixed length buckets and masked
        pads, every pair within the documented f32 tolerance of the
        scalar float64 golden — on the chip, through the compiled
        scan."""
        from spark_examples_tpu.ops.pairhmm import (
            PAIRHMM_FORWARD_ATOL,
            PAIRHMM_FORWARD_RTOL,
            pairhmm_bucket,
            pairhmm_forward_batch,
            pairhmm_forward_ref,
        )

        rng = np.random.default_rng(0)
        pairs = _pairs(
            rng,
            [(1, 8), (7, 16), (37, 64), (100, 116), (100, 200)],
        )
        r_b = pairhmm_bucket(max(p[0].size for p in pairs))
        h_b = pairhmm_bucket(max(p[2].size for p in pairs))
        out = np.asarray(
            pairhmm_forward_batch(
                *_batch(pairs, r_b, h_b),
                np.float32(45.0),
                np.float32(10.0),
            )
        )
        refs = np.array(
            [pairhmm_forward_ref(r, q, h) for r, q, h in pairs]
        )
        np.testing.assert_allclose(
            out,
            refs,
            rtol=PAIRHMM_FORWARD_RTOL,
            atol=PAIRHMM_FORWARD_ATOL,
        )

    def test_batch_permutation_is_bit_identical_on_chip(self, tpu):
        """Per-pair values must not depend on tile composition on
        hardware either (the completion-order feed's contract)."""
        from spark_examples_tpu.ops.pairhmm import (
            pairhmm_bucket,
            pairhmm_forward_batch,
        )

        rng = np.random.default_rng(3)
        pairs = _pairs(rng, [(50, 80)] * 16)
        r_b, h_b = pairhmm_bucket(50), pairhmm_bucket(80)
        base = np.asarray(
            pairhmm_forward_batch(
                *_batch(pairs, r_b, h_b),
                np.float32(45.0),
                np.float32(10.0),
            )
        )
        perm = rng.permutation(len(pairs))
        shuffled = np.asarray(
            pairhmm_forward_batch(
                *_batch([pairs[i] for i in perm], r_b, h_b),
                np.float32(45.0),
                np.float32(10.0),
            )
        )
        np.testing.assert_array_equal(base[perm], shuffled)
