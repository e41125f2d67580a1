"""Pallas scatter-accumulate kernel suite (ops/scatter_kernel.py).

Pins the one-hot-count outer-product formulation bit-identical to the
chunked-scan scatter across OOB sentinels, duplicate carriers, row
blocking, and k buckets — in interpreter mode, so the contract is
testable on the CPU container — plus the dispatcher's env kill switch /
auto-resolution semantics and the end-to-end sparse-engine integration
(``SPARK_EXAMPLES_TPU_SCATTER_KERNEL=interpret`` matches the dense
reference through both the single-device and mesh-sharded
accumulators). The single-device MXU update (device-built carrier-count
panel, int8 contraction) is pinned the same way, with its per-window
selection rule.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_examples_tpu.arrays.blocks import csr_windows
from spark_examples_tpu.ops.gramian import gramian
from spark_examples_tpu.ops.scatter_kernel import (
    MXU_MAX_CARRIER_BUCKET,
    kernel_block_rows,
    resolve_scatter_path,
    scatter_pairs_kernel,
    window_scatter_path,
)
from spark_examples_tpu.ops.sparse import (
    SCATTER_CHUNK_VARIANTS,
    _pad_rows_for_scan,
    _scatter_accumulate_jit,
    padded_carrier_matrix,
    scatter_pairs_chunked,
    sparse_gramian_blockwise,
)
from spark_examples_tpu.parallel.mesh import make_mesh
from spark_examples_tpu.parallel.sharded import (
    _sparse_tile_kernels,
    sparse_sharded_gramian_blockwise,
)

from tests.test_sparse_gramian import cohort_csr


def _random_case(rng, t_r, t_c, v, k, oob_frac=0.2):
    row = rng.integers(0, t_r, size=(v, k)).astype(np.int32)
    col = rng.integers(0, t_c, size=(v, k)).astype(np.int32)
    # Sprinkle OOB sentinels the way the tile re-base does (any index
    # >= the axis size is dropped).
    row[rng.random((v, k)) < oob_frac] = t_r
    col[rng.random((v, k)) < oob_frac] = t_c + 7
    g = rng.integers(0, 9, size=(t_r, t_c)).astype(np.float32)
    return jnp.asarray(g), jnp.asarray(row), jnp.asarray(col)


class TestKernelBitIdentity:
    @pytest.mark.parametrize(
        "t_r,t_c,k",
        [
            (8, 128, 8),
            (64, 128, 16),
            (64, 256, 64),
            (128, 128, 8),
        ],
    )
    def test_matches_scan_across_geometries(self, t_r, t_c, k):
        rng = np.random.default_rng(t_r + t_c + k)
        g, row, col = _random_case(rng, t_r, t_c, SCATTER_CHUNK_VARIANTS * 2, k)
        a = np.asarray(scatter_pairs_chunked(g, row, col))
        b = np.asarray(scatter_pairs_kernel(g, row, col, interpret=True))
        np.testing.assert_array_equal(a, b)

    def test_duplicate_pairs_accumulate_multiply(self):
        # Same (row, col) pair repeated within one variant: scatter-add
        # applies every +1; the one-hot COUNT formulation must too.
        v = SCATTER_CHUNK_VARIANTS
        row = np.full((v, 8), 8, np.int32)  # all OOB (t_r = 8)
        col = np.full((v, 8), 200, np.int32)
        row[0, :4] = 3
        col[0, :4] = 77
        g = jnp.zeros((8, 128), jnp.float32)
        out = np.asarray(
            scatter_pairs_kernel(
                g, jnp.asarray(row), jnp.asarray(col), interpret=True
            )
        )
        assert out[3, 77] == 16.0  # 4 row hits x 4 col hits
        assert out.sum() == 16.0

    def test_all_sentinel_is_inert(self):
        v = SCATTER_CHUNK_VARIANTS
        row = np.full((v, 16), 64, np.int32)
        col = np.full((v, 16), 128, np.int32)
        g0 = np.arange(64 * 128, dtype=np.float32).reshape(64, 128)
        out = np.asarray(
            scatter_pairs_kernel(
                jnp.asarray(g0),
                jnp.asarray(row),
                jnp.asarray(col),
                interpret=True,
            )
        )
        np.testing.assert_array_equal(out, g0)

    def test_row_blocking_covers_tall_tiles(self, monkeypatch):
        # Force a tiny VMEM budget so the kernel must grid over row
        # blocks — the accumulating block is revisited per chunk and
        # the result must not change.
        rng = np.random.default_rng(5)
        g, row, col = _random_case(
            rng, 64, 128, SCATTER_CHUNK_VARIANTS * 2, 16
        )
        want = np.asarray(scatter_pairs_chunked(g, row, col))
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL_VMEM",
            str(SCATTER_CHUNK_VARIANTS * 128 * 4 + 4 * 8 * 128 * 4
                + SCATTER_CHUNK_VARIANTS * 8 * 4
                + 4 * SCATTER_CHUNK_VARIANTS * 16 * 4),
        )
        assert kernel_block_rows(64, 128, 16) == 8
        got = np.asarray(
            scatter_pairs_kernel(g, row, col, interpret=True)
        )
        np.testing.assert_array_equal(want, got)

    def test_oversized_carrier_bucket_falls_back_per_window(
        self, monkeypatch
    ):
        """The resolve-time budget check cannot see K (it varies per
        window): a carrier bucket whose (K, C) index blocks blow the
        budget takes the scan body, decided and COUNTED host-side per
        window — the kernel itself refuses rather than quietly
        switching implementation inside a trace."""
        from spark_examples_tpu import obs

        rng = np.random.default_rng(9)
        k = 64
        g, row, col = _random_case(
            rng, 64, 128, SCATTER_CHUNK_VARIANTS, k
        )
        # Budget passes the resolve-time check (k unknown → 0) but not
        # the dispatch's real-K check.
        budget = (
            SCATTER_CHUNK_VARIANTS * 128 * 4 + 4 * 8 * 128 * 4
            + SCATTER_CHUNK_VARIANTS * 8 * 4
            + 4 * SCATTER_CHUNK_VARIANTS * 8 * 4 + 1024
        )  # fits the smallest bucket (8), not k
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL_VMEM", str(budget)
        )
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "interpret"
        )
        assert resolve_scatter_path((64, 128)) == "interpret"
        assert kernel_block_rows(64, 128, k) is None
        with pytest.raises(ValueError, match="route this window"):
            scatter_pairs_kernel(g, row, col, interpret=True)
        counter = obs.get_registry().counter(
            "sparse_scatter_path_windows_total"
        )
        before = {
            p: counter.labels(path=p).value
            for p in ("fallback-scan", "interpret")
        }
        assert window_scatter_path("interpret", (64, 128), k) == "scan"
        assert window_scatter_path("interpret", (64, 128), 8) == "interpret"
        for p in before:
            assert counter.labels(path=p).value == before[p] + 1


def _mxu_case(case):
    """(n, carrier matrix) for one MXU-update bit-identity case."""
    n = 130  # not a multiple of 128: the Pallas kernel could not run
    rng = np.random.default_rng(len(case))
    v, k_max = {"k8": (256, 8), "k64": (256, 64)}.get(case, (300, 8))
    lens = rng.integers(0, k_max + 1, size=v)
    lens[0] = k_max
    idx = np.concatenate(
        [rng.choice(n, size=k, replace=False) for k in lens]
    )
    mat = padded_carrier_matrix(
        idx, lens, sentinel=n, n_rows=_pad_rows_for_scan(v)
    )
    if case == "sentinel-rows":
        mat[::3] = n
    elif case == "duplicate":
        mat[1, :5] = 7  # one carrier five times in one variant
    return n, mat


class TestMxuUpdate:
    """The scatter route's MXU update: the carrier-count panel built on
    the device, then G += X·Xᵀ — bit-identical to the pair scatter."""

    @pytest.mark.parametrize(
        "case", ["k8", "k64", "tail", "sentinel-rows", "duplicate"]
    )
    def test_matches_scan_and_numpy(self, case):
        n, mat = _mxu_case(case)
        if case == "tail":
            assert mat.shape[0] > 300  # V_pad > V: all-sentinel tail
        g0 = np.random.default_rng(1).integers(0, 9, (n, n))
        g0 = g0.astype(np.float32)
        scan = np.asarray(
            _scatter_accumulate_jit(jnp.asarray(g0), jnp.asarray(mat))
        )
        mxu = np.asarray(
            _scatter_accumulate_jit(
                jnp.asarray(g0), jnp.asarray(mat), path="mxu"
            )
        )
        np.testing.assert_array_equal(mxu, scan)
        x = np.zeros((n + 1, mat.shape[0]), np.int64)
        np.add.at(x, (mat, np.arange(mat.shape[0])[:, None]), 1)
        want = g0 + (x[:n] @ x[:n].T).astype(np.float32)
        np.testing.assert_array_equal(mxu, want)

    @pytest.mark.parametrize(
        "backend,n,k,stream,kill,want",
        [
            ("tpu", 3202, 64, "scan", False, "mxu"),
            ("tpu", 3202, 8, "scan", False, "mxu"),
            ("tpu", 2504, 32, "scan", False, "mxu"),
            ("tpu", 3202, 127, "scan", False, "mxu"),
            ("tpu", 49960, 8, "scan", False, "mxu"),
            ("tpu", 49960, 32, "scan", False, "mxu"),
            ("tpu", 64, 128, "scan", False, "scan"),
            ("tpu", 3202, 128, "scan", False, "scan"),
            ("tpu", 49960, 128, "scan", False, "scan"),
            ("tpu", 4096, 8, "pallas", False, "mxu"),
            ("tpu", 4096, 64, "pallas", False, "mxu"),
            ("tpu", 4096, 128, "pallas", False, "pallas"),
            ("cpu", 3202, 64, "scan", False, "scan"),
            ("gpu", 3202, 64, "scan", False, "scan"),
            ("cpu", 128, 8, "interpret", False, "interpret"),
            ("tpu", 3202, 64, "scan", True, "scan"),
            ("tpu", 4096, 8, "scan", True, "scan"),
        ],
    )
    def test_window_path_follows_the_rule(
        self, monkeypatch, backend, n, k, stream, kill, want
    ):
        """On a single TPU a window its stream would scan takes the MXU
        update, whatever N, if its bucket's counts fit int8, and is
        counted; so does a window its stream would run on the Pallas
        kernel; off the TPU, and under the kill switch, the stream's
        path; the mesh tiles never."""
        import jax

        from spark_examples_tpu import obs

        assert MXU_MAX_CARRIER_BUCKET == 127
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if kill:
            monkeypatch.setenv("SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "0")
        else:
            monkeypatch.delenv(
                "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", raising=False
            )
        counter = obs.get_registry().counter(
            "sparse_scatter_path_windows_total"
        )
        before = counter.labels(path=want).value
        assert (
            window_scatter_path(stream, (n, n), k, single_device=True)
            == want
        )
        assert counter.labels(path=want).value == before + 1
        assert window_scatter_path(stream, (n, n), k) == stream

    def test_engine_on_tpu_rule_matches_dense(self, monkeypatch):
        """sparse_gramian_blockwise with the backend test seeing a TPU:
        every scatter window takes the MXU update (counted), and G
        equals the dense reference."""
        import jax

        from spark_examples_tpu import obs

        n = 130
        x, pair = cohort_csr(n, 300, density=0.015, seed=13)
        want = np.asarray(gramian(x))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", raising=False
        )
        counter = obs.get_registry().counter(
            "sparse_scatter_path_windows_total"
        )
        before = counter.labels(path="mxu").value
        windows = list(csr_windows(iter([pair]), 64))
        got = np.asarray(
            sparse_gramian_blockwise(
                iter(windows), n, density_threshold=0.5, block_variants=64
            )
        )
        np.testing.assert_array_equal(got, want)
        assert counter.labels(path="mxu").value == before + len(windows)


class TestDispatcher:
    def test_kill_switch_forces_scan(self, monkeypatch):
        monkeypatch.setenv("SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "0")
        assert resolve_scatter_path((64, 128)) == "scan"

    def test_interpret_mode_forced(self, monkeypatch):
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "interpret"
        )
        assert resolve_scatter_path((64, 128)) == "interpret"

    def test_auto_on_cpu_is_scan(self, monkeypatch):
        monkeypatch.delenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", raising=False
        )
        # No Mosaic backend on the CPU container: the compiled kernel
        # never engages; the exact historical executable does.
        assert resolve_scatter_path((64, 128)) == "scan"

    def test_ineligible_geometry_falls_back(self, monkeypatch):
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "interpret"
        )
        # Lane-unaligned tile / non-f32 accumulator: scan.
        assert resolve_scatter_path((37, 37)) == "scan"
        assert (
            resolve_scatter_path((64, 128), np.float64) == "scan"
        )

    def test_vmem_budget_guard(self, monkeypatch):
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL_VMEM", "1024"
        )
        assert kernel_block_rows(64, 128) is None
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "interpret"
        )
        assert resolve_scatter_path((64, 128)) == "scan"


class TestEngineIntegration:
    """The kernel through the real accumulators: bit-identical G."""

    def test_single_device_engine_matches_dense(self, monkeypatch):
        n = 128  # lane-aligned so the interpret path engages
        x, pair = cohort_csr(n, 300, density=0.03, seed=11)
        want = np.asarray(gramian(x))
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "interpret"
        )
        assert resolve_scatter_path((n, n)) == "interpret"
        got = np.asarray(
            sparse_gramian_blockwise(
                csr_windows(iter([pair]), 64), n, block_variants=64
            )
        )
        np.testing.assert_array_equal(got, want)

    def test_mesh_engine_matches_dense(self, monkeypatch):
        n = 256  # 2x2 mesh -> (128, 128) tiles, kernel-eligible
        x, pair = cohort_csr(n, 256, density=0.02, seed=12)
        want = np.asarray(gramian(x))
        mesh = make_mesh("data:2,model:2")
        monkeypatch.setenv(
            "SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "interpret"
        )
        got = np.asarray(
            sparse_sharded_gramian_blockwise(
                csr_windows(iter([pair]), 64),
                n,
                mesh,
                block_variants=64,
            )
        )
        np.testing.assert_array_equal(got, want)

    def test_kernel_path_is_part_of_executable_cache_key(self):
        mesh = make_mesh("data:2,model:2")
        a = _sparse_tile_kernels(
            mesh, "data", "model", 256, 128, 128, "float32",
            "int8", "scan",
        )
        b = _sparse_tile_kernels(
            mesh, "data", "model", 256, 128, 128, "float32",
            "int8", "interpret",
        )
        assert a is not b  # distinct cached kernel sets per path
