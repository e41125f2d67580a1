"""The scatter route's device updates and the one rule that picks them
(``ops/sparse.window_scatter_path``).

Pins every update bit-identical to the chunked scan and to numpy on the
CPU — the compare-chain MXU update, the MXU update scatter-built from
the carrier list over the whole G, and the mesh tile update of a pod
step's gathered carrier matrix with and without the mirror partition —
plus the rule's table (backend × device layout × carrier bucket × int8
fit) and the int8 fit check of a gathered matrix. The TPU's choices are
forced on the CPU by a patched ``jax.default_backend``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_examples_tpu import obs
from spark_examples_tpu.arrays.blocks import csr_windows
from spark_examples_tpu.ops.gramian import gramian
from spark_examples_tpu.ops.sparse import (
    MXU_MAX_CARRIER_BUCKET,
    _pad_rows_for_scan,
    _scatter_accumulate_jit,
    carrier_counts_fit_int8,
    carrier_matrix_counts_fit_int8,
    carrier_pairs,
    padded_carrier_matrix,
    scatter_path_rule,
    sparse_gramian_blockwise,
    window_scatter_path,
)
from spark_examples_tpu.parallel.mesh import make_mesh
from spark_examples_tpu.parallel.sharded import (
    _sharded_zeros,
    _sparse_tile_kernels,
    sparse_sharded_gramian_blockwise,
)

from tests.test_sparse_gramian import cohort_csr

PATHS = ("scan", "mxu", "mxu-tile")


def _paths():
    counter = obs.get_registry().counter("sparse_scatter_path_windows_total")
    return {p: counter.labels(path=p).value for p in PATHS}


def _grown(before):
    after = _paths()
    return {p: after[p] - before[p] for p in PATHS if after[p] != before[p]}


def _x_of(idx, lens, n):
    """The window's (n, V) carrier-count matrix, counts exact."""
    x = np.zeros((n, lens.size), np.int64)
    np.add.at(x, (idx, np.repeat(np.arange(lens.size), lens)), 1)
    return x


def _mxu_case(case):
    """(n, carrier matrix) for one MXU-update bit-identity case."""
    n = 130  # not a multiple of 128
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


def _wide_case(case, n=600):
    """(idx, lens) of one window whose largest site needs the bucket the
    case names; ``duplicate`` repeats one carrier at a wide site,
    ``tail`` leaves a variant count that pads, ``all-sentinel`` carries
    no carrier at all."""
    rng = np.random.default_rng(len(case) + 7)
    v = 300 if case == "tail" else 256
    lens = rng.integers(0, 4, size=v)
    if case != "all-sentinel":
        lens[3] = {"k128": 100, "k512": 400}.get(case, 200)
        lens[9] = {"k128": 128, "k512": 512}.get(case, 256)
    else:
        lens[:] = 0
    idx = np.concatenate(
        [rng.choice(n, size=k, replace=False) for k in lens]
    ).astype(np.int64)
    if case == "duplicate":
        start = int(lens[:9].sum())
        idx[start:start + 40] = idx[start]  # one carrier 40 times
    return idx, lens.astype(np.int64)


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
        "backend,layout,k,fit,want",
        [
            ("tpu", "single", 8, True, "mxu"),
            ("tpu", "single", 64, True, "mxu"),
            ("tpu", "single", 127, True, "mxu"),
            ("tpu", "single", 128, True, "mxu-tile"),
            ("tpu", "single", 1024, True, "mxu-tile"),
            ("tpu", "single", 128, False, "scan"),
            ("tpu", "single", 1024, False, "scan"),
            ("tpu", "host-local", 8, True, "mxu-tile"),
            ("tpu", "host-local", 1024, True, "mxu-tile"),
            ("tpu", "host-local", 1024, False, "scan"),
            ("tpu", "pod", 64, True, "mxu-tile"),
            ("tpu", "pod", 1024, True, "mxu-tile"),
            ("tpu", "pod", 1024, False, "scan"),
            ("cpu", "single", 64, True, "scan"),
            ("cpu", "host-local", 1024, True, "scan"),
            ("cpu", "pod", 64, True, "scan"),
            ("gpu", "single", 64, True, "scan"),
            ("gpu", "pod", 1024, True, "scan"),
        ],
    )
    def test_window_path_follows_the_rule(
        self, monkeypatch, backend, layout, k, fit, want
    ):
        """On a TPU a window whose counts fit int8 takes an int8 MXU
        update — the compare-chain panel on one device within bucket
        127, else the scatter-built panels — and the scan where they do
        not; off a TPU always the scan. Host-local and pod tiles follow
        the same rule, and each call is counted under the path taken."""
        assert MXU_MAX_CARRIER_BUCKET == 127
        single = layout == "single"
        assert scatter_path_rule(backend, k, fit, single) == want
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        before = _paths()
        assert window_scatter_path(k, fit, single_device=single) == want
        assert _grown(before) == {want: 1}

    def test_engine_on_tpu_rule_matches_dense(self, monkeypatch):
        """sparse_gramian_blockwise with the backend test seeing a TPU:
        every scatter window takes the MXU update (counted), and G
        equals the dense reference."""
        n = 130
        x, pair = cohort_csr(n, 300, density=0.015, seed=13)
        want = np.asarray(gramian(x))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = _paths()
        windows = list(csr_windows(iter([pair]), 64))
        got = np.asarray(
            sparse_gramian_blockwise(
                iter(windows), n, density_threshold=0.5, block_variants=64
            )
        )
        np.testing.assert_array_equal(got, want)
        assert _grown(before) == {"mxu": len(windows)}


class TestScatterBuiltUpdate:
    """A single-device window above bucket 127: the panel scatter-built
    from its carrier list over the whole G as one tile, contracted on
    the MXU — bit-identical to the scan and to numpy."""

    @pytest.mark.parametrize(
        "case",
        ["k128", "k256", "k512", "tail", "duplicate", "all-sentinel"],
    )
    def test_matches_scan_and_numpy(self, case):
        n = 600
        idx, lens = _wide_case(case, n)
        x = _x_of(idx, lens, n)
        g0 = np.random.default_rng(2).integers(0, 9, (n, n))
        g0 = g0.astype(np.float32)
        n_cols = _pad_rows_for_scan(lens.size)
        mat = padded_carrier_matrix(idx, lens, sentinel=n, n_rows=n_cols)
        if case != "all-sentinel":
            bucket = int(case[1:]) if case.startswith("k") else 256
            assert mat.shape[1] == bucket > MXU_MAX_CARRIER_BUCKET
        tile = np.asarray(
            _scatter_accumulate_jit(
                jnp.asarray(g0),
                jnp.asarray(carrier_pairs(idx, lens, sentinel=n)),
                path="mxu-tile",
                n_cols=n_cols,
            )
        )
        np.testing.assert_array_equal(
            tile, g0 + (x @ x.T).astype(np.float32)
        )
        scan = np.asarray(
            _scatter_accumulate_jit(jnp.asarray(g0), jnp.asarray(mat))
        )
        np.testing.assert_array_equal(tile, scan)


class TestPastInt8:
    """A window whose carrier repeats past int8 at one site takes the
    scan on a TPU — on one device, on a host-local mesh and on a pod
    step — and G stays exact."""

    @pytest.mark.parametrize("layout", ["single", "host-local", "pod"])
    def test_counts_past_int8_take_the_scan(self, monkeypatch, layout):
        n = 300
        idx, lens = _wide_case("k256", n)
        start = int(lens[:9].sum())
        idx[start:start + 130] = idx[start]  # 130 repeats at one site
        assert not carrier_counts_fit_int8(idx, lens, n)
        x = _x_of(idx, lens, n)
        want = (x @ x.T).astype(np.float32)
        offsets = np.concatenate([[0], np.cumsum(lens)])
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = _paths()
        if layout == "single":
            got = sparse_gramian_blockwise(
                csr_windows(iter([(idx, offsets)]), 256), n,
                density_threshold=0.99, block_variants=256,
            )
        elif layout == "host-local":
            got = sparse_sharded_gramian_blockwise(
                csr_windows(iter([(idx, offsets)]), 256), n,
                make_mesh("data:2,model:2"), density_threshold=0.99,
                block_variants=256,
            )
        else:
            mat = padded_carrier_matrix(
                idx, lens, sentinel=n, n_rows=_pad_rows_for_scan(lens.size)
            )
            fit = carrier_matrix_counts_fit_int8(mat, n)
            assert window_scatter_path(mat.shape[1], fit) == "scan"
            got = _pod_update(mat, n, mirror=True, path="scan")
        np.testing.assert_array_equal(np.asarray(got), want)
        assert _grown(before) == {"scan": 1}


def _pod_update(mat, n, mirror, path):
    """One pod step's tile update of a gathered matrix on the 2x2 CPU
    mesh (``n`` even: square tiles, as the mirror needs), then the
    mirror's fill."""
    mesh = make_mesh("data:2,model:2")
    scan, _, _, fill, mxu = _sparse_tile_kernels(
        mesh, "data", "model", n, n // 2, n // 2, "float32", "int8", mirror
    )
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    tiled = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", "model")
    )
    g = _sharded_zeros((n, n), "float32", tiled)()
    carriers = jax.device_put(mat, rep)
    g = mxu(g, carriers) if path == "mxu-tile" else scan(g, carriers)
    return g if fill is None else fill(g)


class TestPodTileUpdate:
    """A pod step's tile update from the gathered padded carrier matrix:
    panels scatter-built with samples = the matrix's entries and sites
    = its rows, under the mirror's slab partition or without it — equal
    to the dense G and to the tile scan."""

    @pytest.mark.parametrize("mirror", [True, False])
    @pytest.mark.parametrize(
        "case", ["wide", "duplicate", "drained-peer", "sentinel-rows"]
    )
    def test_matches_dense_and_scan(self, mirror, case):
        n = 300
        parts = []
        for p in range(2):  # two processes' blocks, as gathered
            idx, lens = _wide_case("duplicate" if case == "duplicate"
                                   else "k256", n)
            idx = (idx + 17 * p) % n
            mat = padded_carrier_matrix(
                idx, lens, sentinel=n, n_rows=256, k_bucket=256
            )
            if case == "drained-peer" and p == 1:
                mat[:] = n
            elif case == "sentinel-rows":
                mat[::5] = n
            parts.append(mat)
        gathered = np.concatenate(parts, axis=0)
        x = np.zeros((n + 1, gathered.shape[0]), np.int64)
        np.add.at(x, (gathered, np.arange(gathered.shape[0])[:, None]), 1)
        want = (x[:n] @ x[:n].T).astype(np.float32)
        assert carrier_matrix_counts_fit_int8(gathered, n)
        tile = np.asarray(_pod_update(gathered, n, mirror, "mxu-tile"))
        np.testing.assert_array_equal(tile, want)
        scan = np.asarray(_pod_update(gathered, n, mirror, "scan"))
        np.testing.assert_array_equal(tile, scan)


class TestMatrixFitCheck:
    @pytest.mark.parametrize("repeats", [0, 1, 127, 128, 200])
    def test_agrees_with_the_window_check(self, repeats):
        """The fit check of a padded (or gathered) carrier matrix gives
        the window check's answer on the same windows, and a gathered
        pair of blocks fits only where both do."""
        n = 1000
        rng = np.random.default_rng(repeats)
        lens = np.array([3, 200, 1, 300], dtype=np.int64)
        idx = np.concatenate(
            [rng.choice(n, size=k, replace=False) for k in lens]
        )
        start = 3 + 200 + 1
        idx[start:start + repeats] = 17  # one carrier, `repeats` times
        fits = carrier_counts_fit_int8(idx, lens, n)
        assert fits is (repeats <= MXU_MAX_CARRIER_BUCKET)
        mat = padded_carrier_matrix(idx, lens, sentinel=n, n_rows=256)
        assert carrier_matrix_counts_fit_int8(mat, n) is fits
        clean = padded_carrier_matrix(
            idx[:3], lens[:1], sentinel=n, n_rows=256,
            k_bucket=mat.shape[1],
        )
        gathered = np.concatenate([clean, mat], axis=0)
        assert carrier_matrix_counts_fit_int8(gathered, n) is fits
        assert carrier_matrix_counts_fit_int8(clean, n)


def _wide_cohort(n, v, seed):
    """(dense X, (indices, offsets)): rare sites with a few past 127
    carriers, so the carrier bucket passes the compare-chain panel's."""
    rng = np.random.default_rng(seed)
    x = (rng.random((n, v)) < 0.01).astype(np.int8)
    for j in range(0, v, 40):
        x[rng.choice(n, size=150, replace=False), j] = 1
    cols, rows = np.nonzero(x.T)
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(np.bincount(cols, minlength=v), out=offsets[1:])
    return x, (rows.astype(np.int64), offsets)


class TestEngineIntegration:
    """The TPU rule through the real accumulators: bit-identical G."""

    def test_single_device_engine_matches_dense(self, monkeypatch):
        n = 260
        x, pair = _wide_cohort(n, 300, seed=11)
        want = np.asarray(gramian(x))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = _paths()
        got = np.asarray(
            sparse_gramian_blockwise(
                csr_windows(iter([pair]), 64), n, block_variants=64,
                density_threshold=0.99,
            )
        )
        np.testing.assert_array_equal(got, want)
        # Every window holds a site of 150 carriers: bucket 256.
        assert _grown(before) == {"mxu-tile": 5}

    def test_mesh_engine_matches_dense(self, monkeypatch):
        n = 256
        x, pair = _wide_cohort(n, 256, seed=12)
        want = np.asarray(gramian(x))
        mesh = make_mesh("data:2,model:2")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = _paths()
        got = np.asarray(
            sparse_sharded_gramian_blockwise(
                csr_windows(iter([pair]), 64),
                n,
                mesh,
                block_variants=64,
                density_threshold=0.99,
            )
        )
        np.testing.assert_array_equal(got, want)
        assert _grown(before) == {"mxu-tile": 4}
