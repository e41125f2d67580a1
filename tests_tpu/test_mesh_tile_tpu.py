"""Real-TPU certification of the host-local mesh stream's int8 MXU tile
update, on a 2x2 mesh of four chips at the ukb50k_exome_rare.mesh
cell's tile shape (49,960^2 G in 24,980^2 tiles).

The main suite pins the update's bit-identity on CPU devices
(tests/test_mesh_pcoa.py); this leg runs the compiled programs on the
chips: one window of rare sites (carrier counts 1 to 999, so the
carrier bucket is 1,024, and one carrier repeated at one site) through
the MXU tile update and through the tile scan, which must agree bit for
bit. The window has 512 sites, two of the scan's chunks, so that the
scan at bucket 1,024 stays within seconds.

jax imports stay inside fixtures/bodies — collection must never
initialize a backend (tests_tpu/conftest.py).
"""

import numpy as np
import pytest

N = 49960
TILE = 24980
SITES = 512


def _window(seed=5):
    rng = np.random.default_rng(seed)
    ks = np.arange(1, 1000)
    lens = rng.choice(ks, size=SITES, p=(1.0 / ks) / (1.0 / ks).sum())
    lens[0], lens[1] = 1, 999
    idx = np.concatenate(
        [rng.choice(N, size=k, replace=False) for k in lens]
    ).astype(np.int64)
    idx[1:4] = idx[1]  # one carrier three times at a site of 999
    return idx, lens.astype(np.int64)


def test_mxu_tile_update_matches_the_scan_on_four_chips(tpu):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_examples_tpu.ops.sparse import (
        _pad_rows_for_scan,
        carrier_counts_fit_int8,
        carrier_pairs,
        padded_carrier_matrix,
    )
    from spark_examples_tpu.parallel.mesh import make_mesh
    from spark_examples_tpu.parallel.sharded import (
        _sharded_zeros,
        _sparse_tile_kernels,
    )

    if len(jax.devices()) < 4:
        pytest.skip("needs four chips")
    mesh = make_mesh("data:2,model:2")
    kernels = _sparse_tile_kernels(
        mesh, "data", "model", N, TILE, TILE, "float32", "int8",
    )
    tiled = NamedSharding(mesh, P("data", "model"))
    rep = NamedSharding(mesh, P(None, None))
    idx, lens = _window()
    assert carrier_counts_fit_int8(idx, lens, N)
    pairs = jax.device_put(carrier_pairs(idx, lens, sentinel=N), rep)
    mat = jax.device_put(
        padded_carrier_matrix(idx, lens, sentinel=N,
                              n_rows=_pad_rows_for_scan(SITES)),
        rep,
    )
    assert mat.shape == (SITES, 1024)
    mxu = kernels[4](_sharded_zeros((N, N), "float32", tiled)(), pairs,
                     n_cols=SITES)
    scan = kernels[0](_sharded_zeros((N, N), "float32", tiled)(), mat)
    assert bool(jnp.array_equal(mxu, scan))
    # G's entries sum to every site's carrier count squared, repeats
    # included; the row sums are exact in float32, their total in f64.
    row_sums = np.asarray(jnp.sum(mxu, axis=1)).astype(np.float64)
    assert row_sums.sum() == float((lens.astype(np.float64) ** 2).sum())
