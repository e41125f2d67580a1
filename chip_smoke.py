#!/usr/bin/env python3
"""Bring-up check: the PCoA main path, end to end, on the attached TPU.

    python chip_smoke.py             one chip: phases ``pca`` and ``sparse``
    python chip_smoke.py --chips 4   four chips: the mesh phase only

Phases (each a function, so tests/test_chip_smoke.py runs them on the CPU
at tiny sizes; only :func:`main` insists on a TPU):

- ``pca``: a seeded 3-subpopulation cohort at N=2,504 × V=65,536 (the
  1000 Genomes phase-3 sample count; ``BASELINE.json``'s north-star
  shape) through ``VariantsPcaDriver`` with ``--pca-mode auto`` — on one
  unsharded chip the packed int8-MXU Gramian plus the fused finish. G must
  equal the host X·Xᵀ exactly; coordinates must be within 1e-4 of the
  float64 MLlib-literal golden after sign alignment.
- ``sparse``: a seeded rare-variant cohort at N=4,096 through
  ``--pca-mode sparse``, so windows take the scatter route. G must equal
  the host X·Xᵀ exactly, and on the chip every scatter window must take
  an int8 MXU update (the compare-chain or the scatter-built panel, as
  its carrier bucket decides), none the scan.
- ``mesh`` (``--chips 4``): the same structured cohort on
  ``--mesh-shape data:2,model:2``, sample-sharded dense and
  ``--pca-mode sparse``, plus the rare-variant cohort through the mesh
  tiles' int8 MXU update: G exact, coordinates against the golden, and
  G's shards on every device of the mesh.

Every time printed is a smoke timing of one cold run (compiles included),
not a metric. The last line of stdout is the JSON result the driver reads;
nothing is printed there when a phase fails or no TPU is attached.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

PCA_SAMPLES, PCA_VARIANTS, PCA_SEED = 2504, 65536, 21
SPARSE_SAMPLES, SPARSE_VARIANTS, SPARSE_SEED, SPARSE_AF = 4096, 16384, 22, 0.005
MESH_SHAPE, MESH_DEVICES = "data:2,model:2", 4
PARITY_BAR = 1e-4


def _say(msg: str) -> None:
    print(msg, flush=True)


class _CompileClock:
    """Backend compile seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def mark(self):
        return self.seconds, self.count


def structured_cohort(n: int, v: int, seed: int):
    from spark_examples_tpu.genomics.fixtures import synthetic_cohort

    t0 = time.perf_counter()
    source = synthetic_cohort(
        n, v, population_structure=3, sparse_calls=True, seed=seed
    )
    _say(f"setup: structured cohort {n}x{v} generated in "
         f"{time.perf_counter() - t0:.1f} s (host)")
    return source


def rare_cohort(n: int, v: int, seed: int, af: float):
    from spark_examples_tpu.genomics.fixtures import synthetic_cohort

    t0 = time.perf_counter()
    source = synthetic_cohort(
        n, v, population_structure=3, sparse_calls=True, seed=seed,
        rare_variant_af=af,
    )
    _say(f"setup: rare-variant cohort {n}x{v} (af {af}) generated in "
         f"{time.perf_counter() - t0:.1f} s (host)")
    return source


def host_gramian(source) -> np.ndarray:
    """X·Xᵀ from the cohort's raw records, independent of the ingest code:
    a sample carries a variant when its genotype has an allele > 0
    (``VariantsPca.scala:58``). Float32 BLAS is exact here — every count
    is an integer below 2^24."""
    index = {cs.id: i for i, cs in enumerate(source._callsets)}
    x = np.zeros((len(index), len(source._variants)), np.float32)
    for j, rec in enumerate(source._variants):
        rows = [
            index[c["callset_id"]]
            for c in rec.get("calls") or ()
            if max(c["genotype"]) > 0
        ]
        x[rows, j] = 1.0
    return x @ x.T


def _driver(source, argv, mesh=None):
    """``cli pca`` as its handler builds it: the CLI's own parser and
    config, then ``VariantsPcaDriver`` over the in-memory cohort."""
    from spark_examples_tpu.cli.main import build_parser
    from spark_examples_tpu.genomics.fixtures import DEFAULT_VARIANT_SET_ID
    from spark_examples_tpu.models.pca import VariantsPcaDriver
    from spark_examples_tpu.utils.config import pca_config_from_args

    conf = pca_config_from_args(build_parser().parse_args(["pca", *argv]))
    conf.variant_set_ids = [DEFAULT_VARIANT_SET_ID]
    return VariantsPcaDriver(conf, source, mesh=mesh)


def _parity(source, result, g_ref) -> float:
    from spark_examples_tpu.ops.pcoa import (
        mllib_principal_components_reference,
        normalize_eigvec_signs,
    )

    order = {cs.id: i for i, cs in enumerate(source._callsets)}
    coords = np.zeros((len(order), 2))
    for callset_id, pc1, pc2 in result:
        coords[order[callset_id]] = (pc1, pc2)
    want, _ = mllib_principal_components_reference(g_ref, 2)
    return float(np.abs(normalize_eigvec_signs(coords) - want).max())


def _scatter_counts() -> dict:
    from spark_examples_tpu import obs
    from spark_examples_tpu.ops.sparse import SCATTER_PATHS

    counter = obs.get_registry().counter("sparse_scatter_path_windows_total")
    return {p: counter.labels(path=p).value for p in SCATTER_PATHS}


def _chip_paths(single_device: bool) -> set:
    """The updates the scatter rule gives, on a TPU, a window whose
    carrier counts fit int8 (the smoke's cohorts repeat no carrier),
    whatever its carrier bucket."""
    from spark_examples_tpu.ops.sparse import (
        MXU_MAX_CARRIER_BUCKET,
        scatter_path_rule,
    )

    return {
        scatter_path_rule("tpu", k, True, single_device)
        for k in (MXU_MAX_CARRIER_BUCKET, MXU_MAX_CARRIER_BUCKET + 1)
    }


def _run_pca(name, source, argv, g_ref, clock, mesh=None, n_devices=None,
             want_finish=None):
    """Ingest + finish through the driver; G exact, coordinates at parity,
    and — where ``want_finish`` names one — that finish is the one that
    ran."""
    c0 = clock.mark()
    t0 = time.perf_counter()
    driver = _driver(source, argv, mesh=mesh)
    g = driver.ingest_gramian()
    got = np.asarray(g)
    t_g = time.perf_counter() - t0
    if n_devices is not None:
        used = sorted({s.device.id for s in g.addressable_shards})
        _say(f"{name}: G {g.shape} sharded {g.sharding.spec} over devices "
             f"{used}")
        if len(used) != n_devices:
            raise AssertionError(
                f"{name}: G is on {len(used)} devices, not {n_devices}"
            )
    np.testing.assert_array_equal(got, g_ref)
    _say(f"{name}: G equals host X·Xᵀ exactly")
    from spark_examples_tpu.utils.tracing import StageTimer

    timer = StageTimer()
    t1 = time.perf_counter()
    result = driver.compute_pca(g, timer=timer)
    t_pca = time.perf_counter() - t1
    finish = _finish_that_ran(timer)
    parity = _parity(source, result, g_ref)
    c1 = clock.mark()
    _say(f"{name}: finish that ran: {finish}; parity vs f64 golden "
         f"{parity:.3e} (bar {PARITY_BAR:g})")
    _say(f"{name}: smoke timing (not a metric): ingest+gramian {t_g:.2f} s, "
         f"pca {t_pca:.2f} s, {c1[1] - c0[1]} compiles "
         f"{c1[0] - c0[0]:.1f} s")
    if not parity <= PARITY_BAR:
        raise AssertionError(f"{name}: parity {parity:.3e} > {PARITY_BAR:g}")
    if want_finish is not None and finish != want_finish:
        raise AssertionError(
            f"{name}: expected the {want_finish} finish, {finish} ran"
        )
    return {"parity": parity, "finish": finish}


def _finish_that_ran(timer) -> str:
    """Which finish produced the coordinates, from the notes the driver
    filed while it ran (not from a prediction made before it): the fused
    finish notes its converged residual; AUTO's fall to dense eigh notes
    the rank-deficient fallback."""
    notes = [n for ns in timer.notes.values() for n in ns]
    if any(n.startswith("fused finish rank-deficient") for n in notes):
        return "dense-eigh fallback"
    if any(n.startswith("fused eig residual") and n.endswith("iterations)")
           for n in notes):
        return "fused"
    return "non-fused"


def _run_sparse(name, source, argv, g_ref, clock, require_chip_path,
                mesh=None, n_devices=None):
    """Sparse-engine ingest; G exact, scatter windows counted by path.
    With ``require_chip_path``, every window took an int8 MXU update the
    rule gives on a TPU (one chip: ``mxu`` or ``mxu-tile``; a mesh:
    ``mxu-tile``) — never the scan."""
    before = _scatter_counts()
    c0 = clock.mark()
    t0 = time.perf_counter()
    driver = _driver(source, argv, mesh=mesh)
    g = driver.ingest_gramian()
    got = np.asarray(g)
    t_g = time.perf_counter() - t0
    c1 = clock.mark()
    if n_devices is not None:
        used = sorted({s.device.id for s in g.addressable_shards})
        _say(f"{name}: G sharded {g.sharding.spec} over devices {used}")
        if len(used) != n_devices:
            raise AssertionError(
                f"{name}: G is on {len(used)} devices, not {n_devices}"
            )
    np.testing.assert_array_equal(got, g_ref)
    windows = {
        p: int(v - before[p]) for p, v in _scatter_counts().items()
        if v - before[p]
    }
    _say(f"{name}: G equals host X·Xᵀ exactly; scatter windows by path "
         f"{windows}")
    _say(f"{name}: smoke timing (not a metric): ingest+gramian {t_g:.2f} s, "
         f"{c1[1] - c0[1]} compiles {c1[0] - c0[0]:.1f} s")
    if not windows:
        raise AssertionError(f"{name}: no window took the scatter route")
    allowed = _chip_paths(single_device=mesh is None)
    if require_chip_path and not set(windows) <= allowed:
        raise AssertionError(
            f"{name}: expected every scatter window on an int8 MXU update "
            f"{sorted(allowed)}, got windows {windows}"
        )
    return {"windows": windows}


def phase_pca(clock, n=PCA_SAMPLES, v=PCA_VARIANTS, seed=PCA_SEED):
    source = structured_cohort(n, v, seed)
    g_ref = host_gramian(source)
    return _run_pca(
        "pca", source, ["--pca-mode", "auto"], g_ref, clock,
        want_finish="fused",
    )


def phase_sparse(clock, require_chip_path, n=SPARSE_SAMPLES,
                 v=SPARSE_VARIANTS, seed=SPARSE_SEED, af=SPARSE_AF):
    source = rare_cohort(n, v, seed, af)
    g_ref = host_gramian(source)
    return _run_sparse(
        "sparse", source, ["--pca-mode", "sparse"], g_ref, clock,
        require_chip_path,
    )


def phase_mesh(clock, require_chip_path, n=PCA_SAMPLES, v=PCA_VARIANTS,
               seed=PCA_SEED, sparse_n=SPARSE_SAMPLES,
               sparse_v=SPARSE_VARIANTS, mesh_shape=MESH_SHAPE,
               n_devices=MESH_DEVICES):
    from spark_examples_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(mesh_shape)
    _say("mesh: " + ", ".join(
        f"{ax}={mesh.shape[ax]}" for ax in mesh.axis_names
    ) + " over device ids " + str([d.id for d in mesh.devices.flat]))
    source = structured_cohort(n, v, seed)
    g_ref = host_gramian(source)
    out = {}
    for name, flags in (
        ("mesh-dense", ["--sample-sharded"]),
        ("mesh-sparse", ["--pca-mode", "sparse"]),
    ):
        out[name] = _run_pca(
            name, source, ["--mesh-shape", mesh_shape, *flags], g_ref, clock,
            mesh=mesh, n_devices=n_devices,
        )
    rare = rare_cohort(sparse_n, sparse_v, SPARSE_SEED, SPARSE_AF)
    out["mesh-scatter"] = _run_sparse(
        "mesh-scatter", rare,
        ["--mesh-shape", mesh_shape, "--pca-mode", "sparse"],
        host_gramian(rare), clock, require_chip_path, mesh=mesh,
        n_devices=n_devices,
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: FATAL — no TPU: jax found platform {platform!r}; "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: FATAL — --chips {args.chips} needs "
              f"{args.chips} devices, jax found {len(devices)}",
              file=sys.stderr)
        return 2
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _say(f"device: {device}")

    from spark_examples_tpu.native import _SO, load
    from spark_examples_tpu.utils.compile_cache import enable_persistent_cache

    _say(f"compile cache: {enable_persistent_cache()}")
    _say(f"native ingest library: "
         f"{'loaded ' + _SO if load() is not None else 'NOT loaded'}")
    clock = _CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(clock, require_chip_path=True)
    else:
        phase_pca(clock)
        phase_sparse(clock, require_chip_path=True)
    _say(f"smoke timing (not a metric): all phases "
         f"{time.perf_counter() - t0:.1f} s, {clock.count} compiles "
         f"{clock.seconds:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
