"""Real-TPU certification of the Pallas scatter-accumulate kernel.

ROADMAP item 3's remainder: the main suite pins the kernel's
bit-identity in INTERPRET mode on CPU (tests/test_scatter_kernel.py),
which proves the formulation but not that Mosaic lowers and runs it
bit-exact on hardware — exactly the gap that got the round-3 Pallas
Gramian kernels deleted. This leg runs only with a live TPU backend
(skips cleanly anywhere else, same discipline as tests_tpu/
test_hardware.py): the COMPILED kernel must match the chunked-scan
scatter bit-for-bit on the same chip, through both the raw op and the
sparse blockwise engine that auto-selects it.

jax imports stay inside fixtures/bodies — collection must never
initialize a backend (tests_tpu/conftest.py).
"""

import numpy as np
import pytest


def _window(n, v, density, seed, common_every=0):
    """One synthetic CSR window: (indices, lens) with optional common
    variants (dense columns) interleaved — the mixed shape that drives
    both the sentinel pad and duplicate-free carrier rows."""
    rng = np.random.default_rng(seed)
    x = rng.random((n, v)) < density
    if common_every:
        x[:, ::common_every] = rng.random(
            (n, len(range(0, v, common_every)))
        ) < 0.5
    lens = x.sum(axis=0).astype(np.int64)
    idx = np.concatenate(
        [np.nonzero(x[:, j])[0] for j in range(v)]
    ) if lens.sum() else np.zeros(0, dtype=np.int64)
    return idx.astype(np.int64), lens


class TestScatterKernelOnHardware:
    def test_compiled_kernel_is_selected_and_bit_exact(self, tpu):
        """resolve_scatter_path must pick the Mosaic kernel on the
        chip, and the compiled kernel's G must equal the scan path's
        bit-for-bit — OOB sentinels, duplicates, and row-blocking
        included."""
        import jax.numpy as jnp

        from spark_examples_tpu.ops.scatter_kernel import (
            resolve_scatter_path,
            scatter_pairs_kernel,
        )
        from spark_examples_tpu.ops.sparse import (
            _pad_rows_for_scan,
            padded_carrier_matrix,
            scatter_pairs_chunked,
        )

        n = 512
        path = resolve_scatter_path((n, n), np.dtype(np.float32))
        assert path == "pallas", (
            f"expected the compiled kernel on a TPU backend, got {path}"
        )
        idx, lens = _window(n, 1024, 0.02, seed=3, common_every=97)
        mat = padded_carrier_matrix(
            idx, lens, sentinel=n, n_rows=_pad_rows_for_scan(lens.size)
        )
        g0 = jnp.zeros((n, n), jnp.float32)
        scan = np.asarray(scatter_pairs_chunked(g0, mat, mat))
        g1 = jnp.zeros((n, n), jnp.float32)
        kernel = np.asarray(scatter_pairs_kernel(g1, mat, mat))
        np.testing.assert_array_equal(scan, kernel)
        # Ground truth from numpy: exact pair counts.
        x = np.zeros((n, lens.size), dtype=np.int64)
        cols = np.repeat(np.arange(lens.size), lens)
        x[idx, cols] = 1
        np.testing.assert_array_equal(kernel, (x @ x.T).astype(np.float32))

    def test_sparse_blockwise_engine_matches_scan_fallback(self, tpu):
        """End to end through sparse_gramian_blockwise: the
        auto-selected hardware stream (each window on the MXU update or
        the kernel, as its shape decides) vs the same stream under the
        SPARK_EXAMPLES_TPU_SCATTER_KERNEL=0 kill switch must be
        bit-identical (mixed scatter/dense routing included)."""
        import os

        from spark_examples_tpu.ops.sparse import sparse_gramian_blockwise

        n = 256
        windows = [
            _window(n, 512, 0.01, seed=s) for s in (1, 2)
        ] + [_window(n, 512, 0.3, seed=9)]  # a dense-routed window

        def run():
            return np.asarray(
                sparse_gramian_blockwise(
                    iter(windows), n, block_variants=512
                )
            )

        auto = run()
        prev = os.environ.get("SPARK_EXAMPLES_TPU_SCATTER_KERNEL")
        os.environ["SPARK_EXAMPLES_TPU_SCATTER_KERNEL"] = "0"
        try:
            scan = run()
        finally:
            if prev is None:
                os.environ.pop("SPARK_EXAMPLES_TPU_SCATTER_KERNEL", None)
            else:
                os.environ["SPARK_EXAMPLES_TPU_SCATTER_KERNEL"] = prev
        np.testing.assert_array_equal(auto, scan)

    def test_mxu_update_is_selected_and_bit_exact(self, tpu):
        """At N=3,202 (not lane-aligned, so no Pallas kernel) the dispatch
        must send a 64-carrier-bucket window to the MXU update on the
        chip, and its G must equal the scan path's and numpy's exactly
        — sentinels, a tail of all-sentinel rows and duplicates
        included."""
        import jax.numpy as jnp

        from spark_examples_tpu.ops.scatter_kernel import (
            resolve_scatter_path,
            window_scatter_path,
        )
        from spark_examples_tpu.ops.sparse import (
            _pad_rows_for_scan,
            _scatter_accumulate_jit,
            padded_carrier_matrix,
        )

        n = 3202
        idx, lens = _window(n, 2000, 0.012, seed=4)
        mat = padded_carrier_matrix(
            idx, lens, sentinel=n, n_rows=_pad_rows_for_scan(lens.size)
        )
        assert mat.shape[1] == 64 and mat.shape[0] > lens.size
        mat[0, :3] = mat[0, 0]  # a duplicate carrier within one variant
        stream = resolve_scatter_path((n, n), np.dtype(np.float32))
        assert stream == "scan"
        path = window_scatter_path(stream, (n, n), 64, single_device=True)
        assert path == "mxu"
        scan = np.asarray(
            _scatter_accumulate_jit(jnp.zeros((n, n), jnp.float32), mat)
        )
        mxu = np.asarray(
            _scatter_accumulate_jit(
                jnp.zeros((n, n), jnp.float32), mat, path="mxu"
            )
        )
        np.testing.assert_array_equal(scan, mxu)
        x = np.zeros((n + 1, mat.shape[0]), dtype=np.int64)
        np.add.at(x, (mat, np.arange(mat.shape[0])[:, None]), 1)
        np.testing.assert_array_equal(
            mxu, (x[:n] @ x[:n].T).astype(np.float32)
        )
