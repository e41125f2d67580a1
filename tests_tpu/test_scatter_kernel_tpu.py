"""Real-TPU certification of the scatter route's int8 MXU updates.

The main suite pins each update bit-identical to the chunked scan on the
CPU (tests/test_scatter_kernel.py), with the TPU's choice forced by a
patched backend; this leg runs only with a live TPU backend (skips
cleanly anywhere else, same discipline as tests_tpu/test_hardware.py):
on the chip the rule must pick the compare-chain panel within bucket 127
and the scatter-built panel above it, and each compiled update must
match the scan bit for bit on the same chip, raw and through the sparse
blockwise engine.

jax imports stay inside fixtures/bodies — collection must never
initialize a backend (tests_tpu/conftest.py).
"""

import numpy as np


def _window(n, v, density, seed, common_every=0, wide_every=0):
    """One synthetic CSR window: (indices, lens) with optional common
    variants (dense columns) interleaved, and optional sites of 200
    carriers (a bucket past 127)."""
    rng = np.random.default_rng(seed)
    x = rng.random((n, v)) < density
    if common_every:
        x[:, ::common_every] = rng.random(
            (n, len(range(0, v, common_every)))
        ) < 0.5
    if wide_every:
        for j in range(0, v, wide_every):
            x[rng.choice(n, size=200, replace=False), j] = True
    lens = x.sum(axis=0).astype(np.int64)
    idx = np.concatenate(
        [np.nonzero(x[:, j])[0] for j in range(v)]
    ) if lens.sum() else np.zeros(0, dtype=np.int64)
    return idx.astype(np.int64), lens


def _numpy_g(idx, lens, n):
    x = np.zeros((n, lens.size), dtype=np.int64)
    np.add.at(x, (idx, np.repeat(np.arange(lens.size), lens)), 1)
    return (x @ x.T).astype(np.float32)


class TestScatterKernelOnHardware:
    def test_scatter_built_update_is_selected_and_bit_exact(self, tpu):
        """Above bucket 127 the rule must send a single-device window to
        the scatter-built panel on the chip, and its G must equal the
        scan's and numpy's bit for bit — a duplicate carrier and the
        carrier list's sentinel tail included."""
        import jax.numpy as jnp

        from spark_examples_tpu.ops.sparse import (
            _pad_rows_for_scan,
            _scatter_accumulate_jit,
            carrier_counts_fit_int8,
            carrier_pairs,
            padded_carrier_matrix,
            window_scatter_path,
        )

        n = 512
        idx, lens = _window(n, 1024, 0.02, seed=3, wide_every=97)
        start = int(lens[:97].sum())
        idx[start + 1:start + 4] = idx[start]  # one carrier four times
        fits = carrier_counts_fit_int8(idx, lens, n)
        assert fits
        assert window_scatter_path(256, fits, single_device=True) == (
            "mxu-tile"
        )
        n_rows = _pad_rows_for_scan(lens.size)
        mat = padded_carrier_matrix(idx, lens, sentinel=n, n_rows=n_rows)
        assert mat.shape[1] == 256
        scan = np.asarray(
            _scatter_accumulate_jit(jnp.zeros((n, n), jnp.float32), mat)
        )
        tile = np.asarray(
            _scatter_accumulate_jit(
                jnp.zeros((n, n), jnp.float32),
                carrier_pairs(idx, lens, sentinel=n),
                path="mxu-tile",
                compute_dtype=jnp.int8,
                n_cols=n_rows,
            )
        )
        np.testing.assert_array_equal(scan, tile)
        np.testing.assert_array_equal(tile, _numpy_g(idx, lens, n))

    def test_sparse_blockwise_engine_matches_numpy(self, tpu):
        """End to end through sparse_gramian_blockwise: the hardware
        stream (each window on the update the rule picks for its
        bucket, mixed scatter/dense routing included) must equal the
        dense numpy G exactly."""
        from spark_examples_tpu import obs
        from spark_examples_tpu.ops.sparse import sparse_gramian_blockwise

        n = 256
        windows = [
            _window(n, 512, 0.01, seed=1),
            _window(n, 512, 0.01, seed=2, wide_every=101),
            _window(n, 512, 0.95, seed=9),  # a dense-routed window
        ]
        counter = obs.get_registry().counter(
            "sparse_scatter_path_windows_total"
        )
        before = {
            p: counter.labels(path=p).value for p in ("mxu", "mxu-tile")
        }
        got = np.asarray(
            # A gate above the wide sites' 200 / 256 carrier share.
            sparse_gramian_blockwise(
                iter(windows), n, density_threshold=0.9, block_variants=512
            )
        )
        want = sum(_numpy_g(idx, lens, n) for idx, lens in windows)
        np.testing.assert_array_equal(got, want)
        assert counter.labels(path="mxu").value == before["mxu"] + 1
        assert counter.labels(path="mxu-tile").value == (
            before["mxu-tile"] + 1
        )

    def test_mxu_update_is_selected_and_bit_exact(self, tpu):
        """At N=3,202 the rule must send a 64-carrier-bucket window to
        the compare-chain MXU update on the chip, and its G must equal
        the scan path's and numpy's exactly — sentinels, a tail of
        all-sentinel rows and duplicates included."""
        import jax.numpy as jnp

        from spark_examples_tpu.ops.sparse import (
            _pad_rows_for_scan,
            _scatter_accumulate_jit,
            padded_carrier_matrix,
            window_scatter_path,
        )

        n = 3202
        idx, lens = _window(n, 2000, 0.012, seed=4)
        mat = padded_carrier_matrix(
            idx, lens, sentinel=n, n_rows=_pad_rows_for_scan(lens.size)
        )
        assert mat.shape[1] == 64 and mat.shape[0] > lens.size
        mat[0, :3] = mat[0, 0]  # a duplicate carrier within one variant
        path = window_scatter_path(64, True, single_device=True)
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
