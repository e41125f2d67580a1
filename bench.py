"""North-star benchmark: PCoA distance+eig phase on TPU vs CPU reference.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``

Workload (BASELINE.md): 1000-Genomes-scale cohort — N=2504 samples,
V=65,536 variants, 3 latent subpopulations (distinct allele-frequency
profiles, ~10% mean carrier density). Population structure makes the
top-2 eigenbasis well-separated, so coordinate parity against the f64
MLlib-literal golden is well-defined and ENFORCED here: parity > 1e-4 on
a real backend exits nonzero (a uniform-random cohort has a
near-degenerate spectrum and no meaningful PC2 — and no real cohort
looks like that).

Every mode measured is a path the shipped product executes
(round-5 verdict ask #1): "fused" is ``pcoa_fused_blocks`` — the exact
composition ``VariantsPcaDriver`` runs by default on single-host
unsharded cohorts (``--pca-mode auto``); "stream-packed" is the
``--pca-mode stream`` route; the unpacked dtype modes are reachable via
``SPARK_EXAMPLES_TPU_GRAMIAN``. The JSON carries the product invocation
for each mode.

``value`` is the driver-defined metric samples²·variants/sec for the TPU
phase: host 0/1 blocks → bit-pack → host→device transfer → Gramian →
double-centering → top-2 eigenvectors → **coordinates host-visible**.

Timing: every phase is timed to a HOST READBACK of its result (the
product semantics anyway: coordinates are emitted as TSV), so no number
times the asynchronous dispatch enqueue.

Without a TPU the bench exits nonzero; peaks come from ``DEVICE_PEAKS``
keyed by the device's ``device_kind``.

``vs_baseline`` is the measured speedup over the reference semantics on
CPU: the numpy per-partition dense accumulation exactly as the reference's
PySpark twin does it (``variants_pca.py:54-82``: ``matrix[ix, ix] += 1``
per variant) plus driver-style float64 LAPACK eigendecomposition
(``VariantsPca.scala:225-226``). The reference publishes no numbers
(SURVEY.md §6), so the baseline is measured here, on this machine, on the
same workload — accumulation and eig both **measured in full** (no slice
scaling). A real `pyspark local[4]` anchor is impossible in this image
(no JVM, no pip — BASELINE.md §"Why the Spark baseline is emulated").
"""

import json
import os
import sys
import time
import timeit

import numpy as np

N_SAMPLES = int(os.environ.get("BENCH_SAMPLES", 2504))
BLOCK_V = int(os.environ.get("BENCH_BLOCK_V", 8192))
N_BLOCKS = int(os.environ.get("BENCH_BLOCKS", 8))
N_VARIANTS = BLOCK_V * N_BLOCKS
NUM_PC = 2
# Published single-chip peaks keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
# int8, 819 GB/s HBM). A device that is not here is an error, not a
# default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

# The product surface that executes each measured mode (round-5 verdict
# ask #1: the bench must headline a path the shipped driver runs).
PRODUCT_INVOCATION = {
    "fused": "cli pca  (--pca-mode auto default on single-host unsharded "
    "runs; ops.fused.pcoa_fused_blocks)",
    "stream-packed": "cli pca --pca-mode stream",
    "stream-int8": "cli pca --pca-mode stream with unpacked int8 blocks "
    "(SPARK_EXAMPLES_TPU_GRAMIAN=int8; documents the 8x-bytes path)",
    "stream-f32": "cli pca --pca-mode stream with "
    "SPARK_EXAMPLES_TPU_GRAMIAN=f32 (documents the float-MXU path)",
}


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _require_tpu():
    """The device every result names; exits nonzero without a TPU.

    The bench measures the chip and nothing else: a CPU run would print
    numbers of XLA's CPU backend under a device metric's name.
    """
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(
            "bench: FATAL — no TPU: jax found platform "
            f"{devices[0].platform!r}; bench.py measures the chip only"
        )
        sys.exit(2)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _peaks(device):
    """Published peaks of ``device['kind']`` (``DEVICE_PEAKS``)."""
    try:
        return DEVICE_PEAKS[device["kind"]]
    except KeyError:
        _log(
            f"bench: FATAL — no published peaks for {device['kind']!r}; "
            "add them to DEVICE_PEAKS with their source"
        )
        sys.exit(2)


def make_cohort(seed=0):
    """Structured cohort: 3 subpopulations, distinct allele frequencies."""
    rng = np.random.default_rng(seed)
    pop = rng.integers(0, 3, N_SAMPLES)
    base = rng.random(N_VARIANTS) * 0.12
    shift = (
        (rng.random((3, N_VARIANTS)) < 0.15)
        * rng.random((3, N_VARIANTS))
        * 0.5
    )
    prob = np.clip(base[None, :] + shift[pop], 0, 0.9)
    x = (rng.random((N_SAMPLES, N_VARIANTS)) < prob).astype(np.int8)
    return x


def _best(f, repeat=3):
    f()  # warm (compile, caches)
    return min(timeit.repeat(f, number=1, repeat=repeat))


def measure_link(x_packed):
    """Sync-latency floor and effective host→device bandwidth.

    Both need a true barrier: a 1-element jitted readback. The put itself
    is asynchronous, so bandwidth is measured as
    (barriered put+readback time − latency floor).
    """
    import jax

    tiny = jax.jit(lambda a: a.ravel()[:1])
    small = np.ones((8, 8), np.float32)

    def floor():
        np.asarray(tiny(jax.device_put(small)))

    t_floor = _best(floor, repeat=5)

    def put():
        np.asarray(tiny(jax.device_put(x_packed)))

    t_put = _best(put, repeat=3)
    bw = x_packed.nbytes / max(t_put - t_floor, 1e-9)
    return t_floor, bw


def tpu_phase_times(x):
    """Honest end-to-end phase time per mode; returns dict + headline."""
    import jax
    import jax.numpy as jnp

    from spark_examples_tpu.utils.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    from spark_examples_tpu.ops import gramian_blockwise, pcoa
    from spark_examples_tpu.ops.fused import pcoa_fused_blocks

    blocks = [
        x[:, i : i + BLOCK_V] for i in range(0, N_VARIANTS, BLOCK_V)
    ]

    def run_fused():
        # THE product default (--pca-mode auto single-host): bit-packed
        # double-buffered accumulation (pack/transfer/matmul overlap) +
        # one finish dispatch + one packed readback — identical
        # composition to VariantsPcaDriver's get_similarity_matrix →
        # fused_finish route.
        coords, _, _ = pcoa_fused_blocks(blocks, N_SAMPLES, NUM_PC)
        return coords  # host arrays (synced)

    def run_stream(**kw):
        g = gramian_blockwise(blocks, N_SAMPLES, **kw)
        coords, _ = pcoa(g.astype(jnp.float32), NUM_PC)
        return np.asarray(coords)  # host readback = the barrier

    # Every mode is product-reachable — see PRODUCT_INVOCATION.
    modes = {
        "fused": run_fused,
        "stream-packed": lambda: run_stream(packed=True),
        "stream-int8": lambda: run_stream(
            compute_dtype=jnp.int8, accum_dtype=jnp.int32
        ),
        "stream-f32": lambda: run_stream(compute_dtype=jnp.float32),
    }
    only = os.environ.get("BENCH_MODES")
    if only:
        keep = [m.strip() for m in only.split(",")]
        modes = {k: v for k, v in modes.items() if k in keep}

    from spark_examples_tpu import obs

    times, coords_by_mode = {}, {}
    for name, fn in modes.items():
        _log(f"bench: compiling {name} (N={N_SAMPLES}, V={N_VARIANTS}) ...")
        with obs.span(f"warm:{name}"):
            coords_by_mode[name] = fn()  # warm/compile
        with obs.span(f"steady:{name}"):
            times[name] = _best(fn, repeat=3)
        _log(f"bench: {name} honest steady-state {times[name]:.3f}s")
    best_mode = min(times, key=times.get)
    _log(f"bench: using {best_mode} path")
    return times, best_mode, coords_by_mode[best_mode]


def measure_ingest(x):
    """Host block-production throughput — the ingest-stage sub-metric.

    The round-5 capture put the remaining wall in HOST ingest (38.7 s of
    48.1 s warm all-autosomes: CSR slice → densify → packbits, single
    thread), invisible to the headline PCoA phase number. This measures
    exactly that stage on the bench cohort: the cohort's CSR arrays
    stream through ``packed_blocks_from_csr`` (the production block
    producer) in three modes — python fallback serial, native serial,
    native multi-worker — and the JSON carries blocks/sec per mode so
    BENCH_* rounds track the ingest wall, not just the device phase.
    """
    from spark_examples_tpu.arrays.blocks import packed_blocks_from_csr
    from spark_examples_tpu.native import force_fallback, load

    # The cohort as one CSR pair: per-variant carrier rows, variant-major.
    cols, rows = np.nonzero(x.T)
    indices = rows.astype(np.int64)
    lens = np.bincount(cols, minlength=N_VARIANTS)
    offsets = np.zeros(N_VARIANTS + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    pair = (indices, offsets)
    n_blocks = -(-N_VARIANTS // BLOCK_V)
    auto_workers = min(os.cpu_count() or 1, 4)

    def produce(workers):
        blocks = 0
        for _ in packed_blocks_from_csr(
            iter([pair]), N_SAMPLES, BLOCK_V, workers=workers
        ):
            blocks += 1
        assert blocks == n_blocks

    modes = {}
    native = load() is not None
    if native:
        modes["native-1"] = lambda: produce(1)
        if auto_workers > 1:
            modes[f"native-{auto_workers}"] = lambda: produce(auto_workers)

    def python_serial():
        with force_fallback():
            produce(1)

    modes["python-1"] = python_serial
    times = {name: _best(fn, repeat=3) for name, fn in modes.items()}
    per_sec = {k: round(n_blocks / v, 2) for k, v in times.items()}
    best = min(times, key=times.get)
    for name, t in sorted(times.items()):
        _log(
            f"bench: ingest {name} {t:.3f}s "
            f"({per_sec[name]} blocks/s)"
        )
    return {
        "blocks_per_sec": per_sec,
        "build_seconds": {k: round(v, 4) for k, v in sorted(times.items())},
        "mode_best": best,
        "blocks": n_blocks,
        "block_variants": BLOCK_V,
        "native_available": native,
        "workers_auto": auto_workers,
    }


def measure_compute_bound(peak_bf16_flops):
    """Compute-bound utilization probe, a FIRST-CLASS bench field.

    The headline phase may be bound by the host link, which says nothing
    about whether the chip itself is well-used.
    This probe times a chained-matmul program big enough to amortize
    the sync floor — one host readback of a tiny slice as the barrier,
    the same timing-honesty rule as every phase — and reports effective
    TFLOP/s as ``compute_bound_tflops`` in the JSON.
    """
    import jax
    import jax.numpy as jnp

    n, depth = 2048, 8
    a = jnp.asarray(
        np.random.default_rng(7).random((n, n), np.float32) * 0.01
    )

    @jax.jit
    def chain(m):
        out = m
        for _ in range(depth):
            out = out @ m
        return out.ravel()[:1]

    t = _best(lambda: np.asarray(chain(a)), repeat=3)
    flops = 2.0 * depth * n**3
    return {
        "seconds": round(t, 4),
        "flops": flops,
        "tflops_effective": round(flops / t / 1e12, 3),
        "dtype": "float32",
        "shape": f"{depth}x matmul {n}x{n}",
        "mfu_vs_bf16_peak": round(flops / t / peak_bf16_flops, 6),
    }


def overlapped_roofline(bytes_moved, link_bw, t_floor, flops, peak_int8_ops):
    """Best-case (lower-bound) time model for the DOUBLE-BUFFERED
    stream the product actually runs.

    The round-5 serial model (transfer + sync + compute summed) was
    beaten by the measurement (`roofline_fraction` 1.046 > 1): the
    fused path overlaps pack/transfer with the matmuls, so summing
    terms over-counts exactly what the pipeline hides, and a model the
    measurement beats cannot flag regressions. The overlapped model:
    one sync floor, the LARGER of total-transfer and total-compute
    (the pipeline's steady state), plus one chunk of the smaller term
    (pipeline fill/drain — the first block cannot overlap with
    anything). Always <= the serial sum, so achieved time >= model and
    the fraction is back in (0, 1].
    """
    t_transfer = bytes_moved / link_bw
    t_compute = flops / peak_int8_ops
    fill = min(t_transfer, t_compute) / max(N_BLOCKS, 1)
    t_model = t_floor + max(t_transfer, t_compute) + fill
    return t_model, {
        "transfer_s": round(t_transfer, 4),
        "compute_s": round(t_compute, 6),
        "sync_floor_s": round(t_floor, 4),
        "fill_drain_s": round(fill, 4),
        "serial_sum_s": round(t_transfer + t_floor + t_compute, 4),
        "model": "floor + max(transfer, compute) + min(...)/n_blocks "
        "(double-buffered overlap; serial_sum_s is the pre-round-6 "
        "miscalibrated model, kept for comparison)",
    }


def cpu_reference_time(x):
    """Reference semantics on CPU, measured IN FULL: per-variant numpy
    accumulation (variants_pca.py:67-75) + f64 centering/eig
    (VariantsPca.scala:198-226)."""
    _log(
        f"bench: measuring CPU baseline accumulation in full "
        f"(V={N_VARIANTS}) ..."
    )
    sample_idx = [np.nonzero(x[:, c])[0] for c in range(N_VARIANTS)]
    g = np.zeros((N_SAMPLES, N_SAMPLES), dtype=np.int64)
    t0 = time.perf_counter()
    for idx in sample_idx:
        g[np.ix_(idx, idx)] += 1
    t_accum = time.perf_counter() - t0
    _log(f"bench: baseline accumulation {t_accum:.1f}s (full)")

    from spark_examples_tpu.ops import mllib_principal_components_reference

    t0 = time.perf_counter()
    coords, _ = mllib_principal_components_reference(
        g.astype(np.float64), NUM_PC
    )
    t_eig = time.perf_counter() - t0
    _log(f"bench: baseline eig {t_eig:.1f}s (full)")
    return t_accum + t_eig, coords


def scale_out_sweep():
    """BENCH_SCALE_OUT=1: the biobank N-scaling sweep (ROADMAP item 2).

    Measures the sparse-aware Gramian engine (the ``--pca-mode sparse``
    accumulation path: ``sparse_sharded_gramian_blockwise`` over a mesh
    of every visible device) at N ∈ BENCH_SCALE_NS (default
    ``2504,16384,65536``), holding carriers-per-variant fixed
    (BENCH_SCALE_CARRIERS, default 128 — the rare-variant regime where
    density d = k/N falls as N grows, the biobank AF shape) over
    BENCH_SCALE_V variants (default 2048). Emits ONE JSON line with
    ``sparse_gramian_nnz_per_sec`` per N plus wall time and full
    backend/mesh provenance, so the biobank trajectory is tracked
    across rounds the way warm ingest was. Timing-honesty rule as
    everywhere: each accumulation is timed to a host readback of a G
    element, never a dispatch enqueue.
    """
    import json as _json

    import jax

    from spark_examples_tpu.arrays.blocks import csr_windows
    from spark_examples_tpu.parallel.mesh import make_mesh
    from spark_examples_tpu.parallel.sharded import (
        sparse_sharded_gramian_blockwise,
    )

    device = _require_tpu()
    ns = [
        int(s)
        for s in os.environ.get(
            "BENCH_SCALE_NS", "2504,16384,65536"
        ).split(",")
        if s.strip()
    ]
    carriers = int(os.environ.get("BENCH_SCALE_CARRIERS", 128))
    n_variants = int(os.environ.get("BENCH_SCALE_V", 2048))
    block_v = int(os.environ.get("BENCH_BLOCK_V", 8192))
    mesh = make_mesh()
    mesh_shape = dict(mesh.shape)

    def cohort_pair(n, seed):
        """Rare-variant CSR cohort: ``carriers`` distinct samples per
        variant (capped at N), drawn directly in CSR — no dense
        intermediate even host-side, so the sweep itself scales."""
        rng = np.random.default_rng(seed)
        k = min(carriers, n)
        idx = np.empty(n_variants * k, dtype=np.int64)
        for v in range(n_variants):
            idx[v * k : (v + 1) * k] = rng.choice(n, size=k, replace=False)
        offsets = np.arange(n_variants + 1, dtype=np.int64) * k
        return idx, offsets

    readback = jax.jit(lambda a: a.ravel()[:1])
    sweep = []
    for i, n in enumerate(ns):
        pair = cohort_pair(n, seed=i)
        nnz = int(pair[1][-1])

        def run(pair=pair, n=n):
            g = sparse_sharded_gramian_blockwise(
                csr_windows(iter([pair]), block_v),
                n,
                mesh,
                block_variants=block_v,
            )
            np.asarray(readback(g))  # host readback = the barrier

        _log(f"bench: scale-out N={n} nnz={nnz} (warm) ...")
        run()  # warm: compile + allocator
        t = _best(run, repeat=int(os.environ.get("BENCH_SCALE_REPEAT", 2)))
        sweep.append(
            {
                "n": n,
                "variants": n_variants,
                "nnz": nnz,
                "density": round(nnz / (n * n_variants), 6),
                "seconds": round(t, 4),
                "nnz_per_sec": round(nnz / t, 2),
            }
        )
        _log(
            f"bench: scale-out N={n} {t:.3f}s "
            f"({sweep[-1]['nnz_per_sec']:.0f} nnz/s)"
        )
    pod = _pod_sparse_leg(carriers, block_v)
    sketch = _sketch_scale_leg(carriers, block_v, mesh)
    largest = sweep[-1]
    print(
        _json.dumps(
            {
                "metric": "sparse_gramian_nnz_per_sec",
                "value": largest["nnz_per_sec"],
                "unit": "nnz/s",
                "device": device,
                "provenance": {
                    "device_count": jax.device_count(),
                    "mesh": mesh_shape,
                    "devices": sorted(
                        {d.platform for d in jax.devices()}
                    ),
                    "carriers_per_variant": carriers,
                    "block_variants": block_v,
                    "path": "parallel.sharded."
                    "sparse_sharded_gramian_blockwise "
                    "(cli pca --pca-mode sparse)",
                },
                "sweep": sweep,
                "pod": pod,
                "sketch": sketch,
                "workload": "rare-variant CSR cohort, fixed "
                "carriers-per-variant (density falls as 1/N — the "
                "biobank AF shape)",
                "timing": "host-readback barrier per accumulation",
            }
        )
    )


def _sketch_scale_leg(carriers: int, block_v: int, mesh):
    """The Gramian-free leg of the scale-out sweep: ``--pca-mode
    sketch`` at N past where EVERY exact path refuses. The sparse
    accumulator holds an f32 N×N on this host (all devices of a
    single-process mesh are addressable here), so its 4 GiB footprint
    bound fires above N = 32768; the sketch panel is O(N·(k+p)) and
    keeps going. BENCH_SCALE_SKETCH_NS picks the cohort sizes (default
    ``1048576`` — the 2^20 biobank point; empty string disables),
    BENCH_SCALE_SKETCH_K the component count (default 10). Emits
    ``sketch_samples_per_sec`` per N plus the documented panel bound
    (``ops.sketch.sketch_host_bytes``), the exact path's refused
    footprint, and ``ru_maxrss`` provenance — the measured proof that
    the refusal boundary was actually crossed, not simulated. Timing
    barrier: ``sketch_eig`` returns host ndarrays (coords readback IS
    the sync point)."""
    import resource

    from spark_examples_tpu.arrays.blocks import csr_windows
    from spark_examples_tpu.ops.pcoa import randomized_panel_width
    from spark_examples_tpu.ops.sketch import sketch_eig
    from spark_examples_tpu.parallel.sharded import sharded_sketch_panel

    ns = [
        int(s)
        for s in os.environ.get(
            "BENCH_SCALE_SKETCH_NS", "1048576"
        ).split(",")
        if s.strip()
    ]
    if not ns:
        return {"skipped": "BENCH_SCALE_SKETCH_NS empty"}
    k = int(os.environ.get("BENCH_SCALE_SKETCH_K", 10))
    n_variants = int(os.environ.get("BENCH_SCALE_SKETCH_V", 2048))
    power_iters = int(os.environ.get("BENCH_SCALE_SKETCH_POWER", 0))
    repeat = int(os.environ.get("BENCH_SCALE_REPEAT", 2))
    bound = 4 << 30  # models.pca max_device_bytes / SKETCH_AUTO_G_BYTES
    sweep = []
    for i, n in enumerate(ns):
        rng = np.random.default_rng(1000 + i)
        kc = min(carriers, n)
        idx = np.empty(n_variants * kc, dtype=np.int64)
        for v in range(n_variants):
            idx[v * kc : (v + 1) * kc] = rng.choice(
                n, size=kc, replace=False
            )
        offsets = np.arange(n_variants + 1, dtype=np.int64) * kc
        nnz = int(offsets[-1])
        panel_box = {}

        def run(idx=idx, offsets=offsets, n=n, box=panel_box):
            panel = sharded_sketch_panel(
                lambda: csr_windows(iter([(idx, offsets)]), block_v),
                n,
                k,
                mesh,
                power_iters=power_iters,
                seed=0,
                block_variants=block_v,
            )
            box["panel"] = panel
            coords, _vals = sketch_eig(panel, k)
            assert coords.shape == (n, k)

        _log(f"bench: scale-out sketch N={n} nnz={nnz} (warm) ...")
        run()  # warm: compile + allocator
        t = _best(run, repeat=repeat)
        width = randomized_panel_width(n, k)
        exact_g = 4 * n * n  # f32 N x N, all tiles on this host
        sweep.append(
            {
                "n": n,
                "k": k,
                "panel_width": width,
                "variants": n_variants,
                "nnz": nnz,
                "power_iters": power_iters,
                "seconds": round(t, 4),
                "samples_per_sec": round(n / t, 2),
                "sketch_host_bytes": int(
                    panel_box["panel"].host_peak_bytes
                ),
                "exact_host_g_bytes": exact_g,
                "exact_refused": exact_g > bound,
                "host_bytes_bound": bound,
                "ru_maxrss_bytes": resource.getrusage(
                    resource.RUSAGE_SELF
                ).ru_maxrss
                * 1024,
            }
        )
        _log(
            f"bench: scale-out sketch N={n} {t:.3f}s "
            f"({sweep[-1]['samples_per_sec']:.0f} samples/s, "
            f"exact_refused={sweep[-1]['exact_refused']})"
        )
    largest = sweep[-1]
    return {
        "metric": "sketch_samples_per_sec",
        "value": largest["samples_per_sec"],
        "unit": "samples/s",
        "sweep": sweep,
        "path": "parallel.sharded.sharded_sketch_panel + "
        "ops.sketch.sketch_eig (cli pca --pca-mode sketch)",
        "workload": "rare-variant CSR cohort, fixed "
        "carriers-per-variant; panel footprint O(N*(k+p)) where the "
        "exact N^2 accumulator refuses past N=32768",
        "timing": "host readback of coords via sketch_eig",
    }


_POD_SPARSE_BENCH_WORKER = '''
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from jax.sharding import Mesh

from spark_examples_tpu.parallel.distributed import initialize_from_env
assert initialize_from_env()
from spark_examples_tpu.arrays.blocks import csr_windows
from spark_examples_tpu.parallel.sharded import (
    sparse_sharded_gramian_blockwise,
)

out, n, carriers, n_variants, block_v, repeat = (
    sys.argv[1],
    int(sys.argv[2]),
    int(sys.argv[3]),
    int(sys.argv[4]),
    int(sys.argv[5]),
    int(sys.argv[6]),
)
depth = int(os.environ.get("BENCH_POD_PIPELINE_DEPTH", "2"))
pid, world = jax.process_index(), jax.process_count()
mesh = Mesh(np.array(jax.devices()).reshape(world, 2), ("data", "model"))

# Same rare-variant CSR cohort as the single-controller sweep, drawn
# directly in CSR; every process derives the identical cohort and
# slices its own windows round-robin (the manifest-slice shape).
rng = np.random.default_rng(0)
k = min(carriers, n)
idx = np.empty(n_variants * k, dtype=np.int64)
for v in range(n_variants):
    idx[v * k : (v + 1) * k] = rng.choice(n, size=k, replace=False)
offsets = np.arange(n_variants + 1, dtype=np.int64) * k
windows = list(csr_windows(iter([(idx, offsets)]), block_v))
mine = windows[pid::world]
readback = jax.jit(lambda a: a.ravel()[:1])


def run():
    g = sparse_sharded_gramian_blockwise(
        iter(mine), n, mesh, block_variants=block_v,
        pipeline_depth=depth,
    )
    np.asarray(readback(g))  # host readback = the barrier


def _union(iv):
    iv = sorted(iv)
    merged = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _intersect_seconds(u1, u2):
    i = j = 0
    tot = 0.0
    while i < len(u1) and j < len(u2):
        a = max(u1[i][0], u2[j][0])
        b = min(u1[i][1], u2[j][1])
        if b > a:
            tot += b - a
        if u1[i][1] < u2[j][1]:
            i += 1
        else:
            j += 1
    return tot / 1e6


def _phase_breakdown(trace_path):
    """Per-phase attribution from the emitted span timeline: exchange
    (collective) seconds vs device-dispatch (scatter) seconds vs how
    much of the sync thread's work the pipeline hid behind compute —
    plus the overlap PROOF (scripts/validate_trace.sparse_overlap_proven,
    the ONE predicate the CI leg and the test worker also assert)."""
    import spark_examples_tpu as _pkg

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(_pkg.__file__))),
            "scripts",
        ),
    )
    import validate_trace as _vt

    evs = json.load(open(trace_path))["traceEvents"]

    def spans(name):
        return [
            (e["ts"], e["ts"] + e["dur"], e.get("args", {}))
            for e in evs
            if e.get("ph") == "X" and e.get("name") == name
        ]

    ag = spans("gramian.sparse.allgather")
    slots = spans("gramian.sparse.slot")
    wins = spans("gramian.sparse.window")
    su = _union([[a, b] for a, b, _ in slots])
    wu = _union([[a, b] for a, b, _ in wins])
    slot_s = sum(b - a for a, b in su) / 1e6
    overlap_s = _intersect_seconds(su, wu)
    proven = _vt.sparse_overlap_proven(evs)
    return {
        "collective_seconds": round(
            sum(b - a for a, b, _ in ag) / 1e6, 4
        ),
        "scatter_seconds": round(
            sum(b - a for a, b in wu) / 1e6, 4
        ),
        "sync_slot_seconds": round(slot_s, 4),
        "overlap_seconds": round(overlap_s, 4),
        "overlap_fraction": (
            round(overlap_s / slot_s, 4) if slot_s > 0 else 0.0
        ),
        "overlap_proven": bool(proven),
    }


run()  # warm: compile + allocator
times = []
phases = None
for i in range(repeat):
    traced = pid == 0 and i == repeat - 1
    if traced:
        from spark_examples_tpu.obs import telemetry_session

        with telemetry_session(trace_out=out + ".trace.json"):
            t0 = time.perf_counter()
            run()
            times.append(time.perf_counter() - t0)
        phases = _phase_breakdown(out + ".trace.json")
    else:
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
if pid == 0:
    with open(out, "w") as f:
        json.dump(
            {
                "seconds": min(times),
                "nnz": int(offsets[-1]),
                "n": n,
                "variants": n_variants,
                "device_count": jax.device_count(),
                "mesh": {"data": world, "model": 2},
                "pipeline_depth": depth,
                "phases": phases,
            },
            f,
        )
'''


def _pod_sparse_leg(carriers: int, block_v: int):
    """The pod-sparse scale-out leg: the same rare-variant sweep
    through ``sparse_sharded_gramian_blockwise`` on a REAL
    ``jax.distributed`` multi-process CPU mesh (the carrier-allgather
    protocol), so the multichip trajectory tracks the pod route like
    r01–r05 tracked the host-local one. BENCH_SCALE_PROCESSES sets the
    process count (default 2; 0 disables), BENCH_SCALE_POD_N the
    cohort size (default 2048). Returns the pod sample dict (with
    process-count + mesh provenance) or an ``{"error": ...}`` record
    on hosts whose backend lacks multi-process CPU collectives — the
    sweep JSON stays parseable either way.
    """
    import json as _json
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    import shutil

    nprocs = int(os.environ.get("BENCH_SCALE_PROCESSES", "2"))
    if nprocs < 2:
        return {"skipped": "BENCH_SCALE_PROCESSES < 2"}
    # Pin each pod-sim process to its own core slice (cores/nprocs
    # cores each) when the host can: a real pod gives every process
    # its own host's cores; unpinned on one machine, N XLA runtimes
    # each size their intra-op pools to ALL cores and thrash each
    # other — a sim artifact, not a protocol cost. Recorded in the
    # sample's provenance either way.
    cores = os.cpu_count() or 1
    pin = shutil.which("taskset") is not None and cores >= nprocs
    slice_width = max(1, cores // nprocs)

    def _pin_prefix(rank):
        if not pin:
            return []
        lo = (rank * slice_width) % cores
        hi = lo + slice_width - 1
        return ["taskset", "-c", f"{lo}-{hi}" if hi > lo else str(lo)]
    n = int(os.environ.get("BENCH_SCALE_POD_N", "2048"))
    n_variants = int(os.environ.get("BENCH_SCALE_POD_V", "512"))
    repeat = int(os.environ.get("BENCH_SCALE_REPEAT", 2))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    _log(
        f"bench: pod-sparse leg N={n} V={n_variants} "
        f"processes={nprocs} ..."
    )
    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, "pod_worker.py")
        with open(script, "w") as f:
            f.write(_POD_SPARSE_BENCH_WORKER)
        out = os.path.join(tmp, "pod.json")
        env = {
            **os.environ,
            "PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": str(nprocs),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        }
        procs = [
            subprocess.Popen(
                _pin_prefix(i)
                + [
                    _sys.executable,
                    script,
                    out,
                    str(n),
                    os.environ.get("BENCH_SCALE_CARRIERS", str(carriers)),
                    str(n_variants),
                    str(block_v),
                    str(repeat),
                ],
                env={**env, "JAX_PROCESS_ID": str(i)},
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
            for i in range(nprocs)
        ]
        logs = []
        try:
            for p in procs:
                logs.append(p.communicate(timeout=600)[0].decode())
        except subprocess.TimeoutExpired:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            return {"error": "pod-sparse leg timed out", "processes": nprocs}
        if any(p.returncode != 0 for p in procs):
            tails = [log[-400:] for log in logs]
            return {
                "error": "pod-sparse worker failed",
                "processes": nprocs,
                "tails": tails,
            }
        with open(out) as f:
            rec = _json.load(f)
    rec["processes"] = nprocs
    rec["pinned"] = pin
    rec["nnz_per_sec"] = round(rec["nnz"] / rec["seconds"], 2)
    rec["seconds"] = round(rec["seconds"], 4)
    rec["path"] = (
        "parallel.sharded.sparse_sharded_gramian_blockwise "
        "(_synced_carrier_stream pod protocol)"
    )
    _log(
        f"bench: pod-sparse N={n} processes={nprocs} "
        f"{rec['seconds']:.3f}s ({rec['nnz_per_sec']:.0f} nnz/s)"
    )
    return rec


def cold_start_bench():
    """BENCH_COLD=1: the cold-start trajectory metric (ROADMAP item 3).

    Serves a fixture cohort over loopback HTTP and measures, with the
    mirror cache EMPTIED before each timed run:

    - ``cold_ingest_seconds``  — streaming cold ingest (``--cold-stream``
      default: wire frames straight into the fetch→decode→build→put
      pipeline, mirror written through in the background);
    - ``phased_cold_seconds``  — the pre-cold-stream path
      (``--no-cold-stream``: full mirror download, then ingest);
    - ``warm_ingest_seconds``  — the same run over the completed mirror
      (the write-through download is awaited first, so warm is truly
      warm);
    - ``cold_to_warm_ratio``   — the ROADMAP target tracks this ≤ 2.

    Timing-honesty rule as everywhere: each ingest is timed to a host
    readback of a G element, never a dispatch enqueue. One JSON line
    with full backend provenance, like every other bench mode;
    BENCH_TRACE_OUT/BENCH_METRICS_OUT/BENCH_MANIFEST_OUT emit the
    telemetry artifacts validate_trace.py schema-checks in CI.
    """
    import json as _json
    import shutil
    import tempfile

    from spark_examples_tpu import obs
    from spark_examples_tpu.genomics import mirror as mirror_mod
    from spark_examples_tpu.genomics.fixtures import (
        DEFAULT_VARIANT_SET_ID,
        synthetic_cohort,
    )
    from spark_examples_tpu.genomics.service import (
        GenomicsServiceServer,
        HttpVariantSource,
    )
    from spark_examples_tpu.genomics.sources import JsonlSource
    from spark_examples_tpu.models.pca import VariantsPcaDriver
    from spark_examples_tpu.obs.session import TelemetrySession
    from spark_examples_tpu.utils.config import PcaConfig

    device = _require_tpu()
    import jax

    refs = "17:41196311:41277499"
    n = int(os.environ.get("BENCH_COLD_SAMPLES", 120))
    v = int(os.environ.get("BENCH_COLD_VARIANTS", 2500))
    workers = int(os.environ.get("BENCH_COLD_WORKERS", 4))
    # Simulated wire RTT (seconds). Loopback has ~zero latency, where
    # the phased bulk copy is legitimately competitive; the streaming
    # cold path's win is LATENCY HIDING, so BENCH_COLD_RTT shapes the
    # served cohort like a remote wire (per-shard RTT + throughput-
    # shaped exports) to measure that regime on demand.
    rtt = float(os.environ.get("BENCH_COLD_RTT", 0))
    workdir = tempfile.mkdtemp(prefix="bench-cold-")
    root = os.path.join(workdir, "cohort")
    synthetic_cohort(n, v, references=refs, seed=3).dump(root)
    local = JsonlSource(root)
    local.ensure_serving_index()

    class _LatencyShaped:
        """Per-request RTT + per-chunk export delay, both paths."""

        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def stream_carrying_frame(self, *args, **kwargs):
            time.sleep(rtt)
            return self._inner.stream_carrying_frame(*args, **kwargs)

        def export_lines(self, name):
            lines = self._inner.export_lines(name)

            def gen():
                for i, line in enumerate(lines):
                    if i % 20 == 0:
                        time.sleep(rtt / 2)
                    yield line

            return gen()

        def ensure_sidecar(self):
            time.sleep(5 * rtt)
            return self._inner.ensure_sidecar()

    served = _LatencyShaped(local) if rtt > 0 else local
    server = GenomicsServiceServer(served).start()
    url = f"http://127.0.0.1:{server.port}"

    def timed_ingest(src):
        import contextlib

        conf = PcaConfig(
            references=refs,
            variant_set_ids=[DEFAULT_VARIANT_SET_ID],
            bases_per_partition=10_000,
            ingest_workers=workers,
        )
        # Driver parity prints ("Matrix size: N") go to stderr here:
        # the bench contract is ONE JSON line on stdout.
        # The timer starts BEFORE driver construction: building the
        # callset index resolves the mirror, which on the phased path
        # IS the cold download — excluding it would time the phased
        # run as if it were warm.
        with contextlib.redirect_stdout(sys.stderr):
            t0 = time.perf_counter()
            drv = VariantsPcaDriver(conf, src)
            g = drv.get_similarity_matrix_csr(drv.get_csr_fused())
            np.asarray(g)  # host readback = the barrier
            return time.perf_counter() - t0

    def fresh_cache(tag):
        cache = os.path.join(workdir, f"cache-{tag}")
        shutil.rmtree(cache, ignore_errors=True)  # EMPTY before timing
        return cache

    outs = {
        "trace_out": os.environ.get("BENCH_TRACE_OUT") or None,
        "metrics_out": os.environ.get("BENCH_METRICS_OUT") or None,
        "manifest_out": os.environ.get("BENCH_MANIFEST_OUT") or None,
    }
    # Warm the accumulate executables on the run's exact shapes FIRST:
    # every timed run below must measure ingest, not the first-call XLA
    # compile (which would land on whichever run went first and corrupt
    # the cold/warm comparison).
    timed_ingest(local)
    try:
        with TelemetrySession(
            **outs,
            command="bench-cold",
            config={"samples": n, "variants": v, "workers": workers},
        ):
            cache = fresh_cache("stream")
            src = HttpVariantSource(url, cache_dir=cache, cold_stream=True)
            with obs.span("cold_stream_ingest"):
                t_stream = timed_ingest(src)
            _log(f"bench: cold streaming ingest {t_stream:.3f}s")
            # Await the write-through mirror so warm is truly warm — and
            # REFUSE to report a ratio if it is not: a failed/unfinished
            # write-through would make the "warm" leg a second cold run
            # and cold_to_warm_ratio a silent lie. If the download beat
            # the driver's cold probe (tiny cohort over raw loopback),
            # the source already upgraded to the mirror tier and
            # t_stream timed a warm read labeled cold — refuse that too
            # rather than publish it.
            stream_mirror = src._resolve_mirror()
            if not mirror_mod.is_cold_stream(stream_mirror):
                raise RuntimeError(
                    "cold streaming leg was not cold (write-through "
                    "finished before the driver's probe); enlarge the "
                    "workload via BENCH_COLD_SAMPLES/BENCH_COLD_VARIANTS "
                    "or add BENCH_COLD_RTT"
                )
            if not stream_mirror.join(timeout=120):
                raise RuntimeError(
                    "write-through mirror did not complete within 120s; "
                    "cold_to_warm_ratio would be mismeasured"
                )
            warm_src = HttpVariantSource(url, cache_dir=cache)
            if warm_src.cold_stream_active():
                raise RuntimeError(
                    "mirror incomplete after write-through (download "
                    "failed?); refusing to time a cold run as warm"
                )
            with obs.span("warm_ingest"):
                t_warm = timed_ingest(warm_src)
            _log(f"bench: warm ingest {t_warm:.3f}s")
            with obs.span("phased_cold_ingest"):
                t_phased = timed_ingest(
                    HttpVariantSource(
                        url,
                        cache_dir=fresh_cache("phased"),
                        cold_stream=False,
                    )
                )
            _log(f"bench: cold phased ingest {t_phased:.3f}s")
    finally:
        server.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    print(
        _json.dumps(
            {
                "metric": "cold_ingest_seconds",
                "value": round(t_stream, 4),
                "unit": "s",
                "cold_to_warm_ratio": round(t_stream / t_warm, 3),
                "phased_cold_seconds": round(t_phased, 4),
                "warm_ingest_seconds": round(t_warm, 4),
                "vs_phased": round(t_phased / t_stream, 3),
                "device": device,
                "provenance": {
                    "device_count": jax.device_count(),
                    "devices": sorted(
                        {d.platform for d in jax.devices()}
                    ),
                    "transport": "http-loopback",
                    "simulated_rtt_s": rtt,
                    "ingest_workers": workers,
                    "path": "cli pca --api-url ... --cache-dir ... "
                    "--cold-stream (HttpVariantSource cold-stream tier)",
                },
                "note": "vs_phased compares against --no-cold-stream on "
                "the same server; set BENCH_COLD_RTT to shape the "
                "loopback like a remote wire (per-shard RTT + "
                "throughput-limited exports) — the >=2x streaming bar "
                "is enforced in tests/test_cold_stream.py",
                "workload": {
                    "samples": n,
                    "variants": v,
                    "references": refs,
                },
                "cache": "mirror cache EMPTIED before each cold run; "
                "warm run awaits the write-through mirror",
                "timing": "host-readback barrier per ingest",
            }
        )
    )


def serving_bench():
    """BENCH_SERVE=1: the serving-throughput leg (ROADMAP item 1).

    Measures the two marginal-job optimizations of the incremental +
    batched serving tier (docs/OPERATIONS.md §4c), with per-job results
    asserted BIT-IDENTICAL between the compared paths before anything
    is reported:

    - ``serial_jobs_per_sec`` vs ``gang_jobs_per_sec`` — a queue soak
      of BENCH_SERVE_JOBS small-cohort submissions (BENCH_SERVE_COHORT
      samples each, rotating sample windows so nothing dedups), drained
      by one worker step loop with gang batching off vs on;
    - ``cold_seconds`` vs ``delta_seconds`` — a ±16-sample cohort tweak
      (8 removed + 8 added against a cached ancestor) executed
      from-scratch vs through the delta index's rank-k correction.

    jit executables are warmed on the exact shapes first (throwaway
    serial job + throwaway 2-gang), so the timed legs measure serving,
    not first-call XLA compiles. One JSON line on stdout, full backend
    provenance; BENCH_TRACE_OUT/BENCH_METRICS_OUT emit the telemetry
    artifacts (job.gang/job.delta spans, serving_delta_jobs_total,
    serving_gang_size) that validate_trace.py schema-checks in CI.
    """
    import contextlib
    import json as _json

    from spark_examples_tpu.genomics.fixtures import (
        DEFAULT_VARIANT_SET_ID,
        synthetic_cohort,
    )
    from spark_examples_tpu.obs.session import TelemetrySession
    from spark_examples_tpu.serving import (
        AnalysisEngine,
        AnalysisJobTier,
        JobSpec,
    )
    from spark_examples_tpu.utils.config import PcaConfig

    device = _require_tpu()
    import jax

    refs = "17:41196311:41277499"
    n = int(os.environ.get("BENCH_SERVE_SAMPLES", 96))
    v = int(os.environ.get("BENCH_SERVE_VARIANTS", 8000))
    jobs = int(os.environ.get("BENCH_SERVE_JOBS", 12))
    cohort_n = int(os.environ.get("BENCH_SERVE_COHORT", 48))
    # Cohort allele-frequency shape: the default is the biobank
    # rare-variant regime the serving tier targets (same af the
    # acceptance test pins); 0 = the historical common-variant draw.
    af = float(os.environ.get("BENCH_SERVE_AF", 0.02))
    delta_k = 16  # the acceptance shape: ±16-sample cohort
    src = synthetic_cohort(
        n,
        v,
        references=refs,
        seed=5,
        sparse_calls=True,
        rare_variant_af=af or None,
    )
    ids = [f"{DEFAULT_VARIANT_SET_ID}-{i}" for i in range(n)]
    base = PcaConfig(
        variant_set_ids=[DEFAULT_VARIANT_SET_ID],
        references=refs,
        bases_per_partition=20_000,
        block_variants=512,
        ingest_workers=2,
    )
    # Rotating sample windows: every job a distinct small cohort with
    # the same variant params — dedup never fires, gangs always can.
    specs = [
        JobSpec(
            samples=tuple(
                sorted(ids[(i * 7 + j) % n] for j in range(cohort_n))
            )
        )
        for i in range(jobs)
    ]

    def drain(tier):
        # timeout=0: the queue is fully pre-filled and workers=0, so a
        # blocking final pop would put its whole wait inside the timed
        # window — at gang scale (one dispatch) that tail would be a
        # large fraction of the measurement.
        while tier.step(timeout=0.0):
            pass

    def soak(gang_max):
        tier = AnalysisJobTier(
            AnalysisEngine(src),
            base,
            workers=0,
            queue_depth=max(64, jobs + 1),
            tenant_quota=max(64, jobs + 1),
            gang_max_samples=gang_max,
        )
        submitted = [tier.submit(s)[0] for s in specs]
        t0 = time.perf_counter()
        drain(tier)
        dt = time.perf_counter() - t0
        rows = [j.result for j in submitted]
        assert all(j.state == "done" for j in submitted), [
            (j.id, j.error) for j in submitted if j.state != "done"
        ]
        tier.close()
        return dt, rows

    outs = {
        "trace_out": os.environ.get("BENCH_TRACE_OUT") or None,
        "metrics_out": os.environ.get("BENCH_METRICS_OUT") or None,
        "manifest_out": os.environ.get("BENCH_MANIFEST_OUT") or None,
    }
    with contextlib.redirect_stdout(sys.stderr):
        # Warm the executables on the run's exact shapes: one serial
        # job (cohort-shaped blocks + finish) and one 2-gang (the
        # batched accumulator), outside every timed window.
        warm = AnalysisJobTier(
            AnalysisEngine(src), base, workers=0, gang_max_samples=0
        )
        warm.submit(specs[0])
        drain(warm)
        warm.close()
        warm2 = AnalysisJobTier(
            AnalysisEngine(src),
            base,
            workers=0,
            gang_max_samples=cohort_n,
        )
        warm2.submit(specs[0])
        warm2.submit(specs[1])
        drain(warm2)
        warm2.close()
        with TelemetrySession(
            **outs,
            command="bench-serve",
            config={
                "samples": n,
                "variants": v,
                "jobs": jobs,
                "cohort": cohort_n,
            },
        ):
            # Best-of-N on every timed leg (the `_best` discipline the
            # other bench modes use): this container shares its host,
            # and a scheduler stall inside a single measurement would
            # report serving noise as a regression. Each soak repeat
            # builds a FRESH tier — reusing one would serve repeats
            # from its result cache.
            repeat = int(os.environ.get("BENCH_SERVE_REPEAT", 2))
            serial_runs = [soak(gang_max=0) for _ in range(repeat)]
            gang_runs = [soak(gang_max=cohort_n) for _ in range(repeat)]
            t_serial, rows_serial = min(serial_runs, key=lambda r: r[0])
            t_gang, rows_gang = min(gang_runs, key=lambda r: r[0])
            assert rows_serial == rows_gang, (
                "gang-batched results diverged from serial — refusing "
                "to report throughput for wrong answers"
            )
            # Introspection-plane leg (docs/OBSERVABILITY.md §live
            # endpoints): the /metrics exposition itself, and its cost
            # to the hot path. Two numbers: scrape latency quantiles
            # over the registry the soaks just populated, and the
            # serial soak re-run under a 1 Hz background scraper — the
            # Prometheus cadence — whose throughput must be unchanged
            # (zero-delta pin; the scrape path takes only per-metric
            # locks, never the tier lock).
            import threading as _threading

            from spark_examples_tpu import obs as _obs

            reg = _obs.get_registry()
            lat = []
            for _ in range(200):
                t0 = time.perf_counter()
                reg.to_prometheus()
                lat.append(time.perf_counter() - t0)
            lat.sort()
            scrape_p50_ms = lat[len(lat) // 2] * 1e3
            scrape_p99_ms = lat[min(len(lat) - 1, (len(lat) * 99) // 100)] * 1e3

            def soak_scraped():
                stop = _threading.Event()

                def scrape_loop():
                    while True:
                        reg.to_prometheus()
                        if stop.wait(1.0):
                            return

                t = _threading.Thread(target=scrape_loop, daemon=True)
                t.start()
                try:
                    return soak(gang_max=0)
                finally:
                    stop.set()
                    t.join()

            # Adjacent baseline: t_serial above may have absorbed a
            # late compile (the near-degenerate retry executable), so
            # the overhead ratio compares against a fresh no-scraper
            # soak measured back to back with the scraped ones.
            plain_runs = [soak(gang_max=0) for _ in range(repeat)]
            t_plain = min(r[0] for r in plain_runs)
            scraped_runs = [soak_scraped() for _ in range(repeat)]
            t_scraped, rows_scraped = min(scraped_runs, key=lambda r: r[0])
            assert rows_scraped == rows_serial, (
                "results changed under a background /metrics scraper — "
                "observation must not perturb the system"
            )
            scrape_overhead = t_scraped / t_plain
            assert scrape_overhead <= 1.5, (
                f"1 Hz /metrics scraper cost {scrape_overhead:.2f}x on "
                "serving throughput (best-of-N) — the scrape path is "
                "supposed to be off the hot path entirely"
            )
            # Delta leg: ancestor cohort cached, then the ±16 tweak.
            anc = tuple(sorted(ids[:cohort_n]))
            tweak = tuple(
                sorted(ids[delta_k // 2 : cohort_n + delta_k // 2])
            )
            cold_engine = AnalysisEngine(src)
            cold_conf = PcaConfig(
                **{
                    **base.__dict__,
                    "samples": list(tweak),
                }
            )
            # Warm the TARGET cohort end to end on a throwaway engine:
            # a near-degenerate spectrum makes the fused finish retry
            # with doubled iterations — a NEW executable whose ~1s
            # compile would otherwise land in whichever timed leg hits
            # it first and corrupt the cold/delta comparison both ways.
            AnalysisEngine(src).run(cold_conf)
            t_cold = float("inf")
            for _ in range(max(1, repeat)):
                t0 = time.perf_counter()
                rows_cold = cold_engine.run(cold_conf)
                t_cold = min(t_cold, time.perf_counter() - t0)
            anc_conf = PcaConfig(
                **{**base.__dict__, "samples": list(anc)}
            )
            # Warm-tweak: a throwaway ±delta job of the SAME shape
            # class (remove 8 / add 8 against the cached ancestor — a
            # different cohort, so nothing short-circuits on the exact
            # key) compiles the correction executable outside the
            # timed window, the rule every other leg follows.
            warm_tweak = tuple(
                sorted(
                    ids[: cohort_n - delta_k // 2]
                    + ids[cohort_n : cohort_n + delta_k // 2]
                )
            )
            warm_conf = PcaConfig(
                **{**base.__dict__, "samples": list(warm_tweak)}
            )

            def delta_once():
                # A FRESH engine per repeat: re-running the tweak on
                # one engine would resolve its own cached result as an
                # exact-frame hit and time the zero-delta return, not
                # the rank-k correction.
                eng = AnalysisEngine(src, delta_max_samples=delta_k)
                eng.run(anc_conf)  # cache the ancestor (cold)
                assert eng.delta_resolvable(warm_conf)
                eng.run(warm_conf)
                assert eng.delta_resolvable(cold_conf)
                t0 = time.perf_counter()
                rows = eng.run(cold_conf)
                return time.perf_counter() - t0, rows

            delta_runs = [delta_once() for _ in range(max(1, repeat))]
            t_delta, rows_delta = min(delta_runs, key=lambda r: r[0])
            assert rows_delta == rows_cold, (
                "delta-served rows diverged from cold — refusing to "
                "report a speedup for wrong answers"
            )
    print(
        _json.dumps(
            {
                "metric": "serving_jobs_per_sec",
                "serial_jobs_per_sec": round(jobs / t_serial, 3),
                "gang_jobs_per_sec": round(jobs / t_gang, 3),
                "gang_speedup": round(t_serial / t_gang, 3),
                "cold_seconds": round(t_cold, 4),
                "delta_seconds": round(t_delta, 4),
                "delta_speedup": round(t_cold / t_delta, 3),
                "delta_samples_changed": delta_k,
                "metrics_scrape_p50_ms": round(scrape_p50_ms, 4),
                "metrics_scrape_p99_ms": round(scrape_p99_ms, 4),
                "scrape_overhead_ratio": round(scrape_overhead, 3),
                "bit_identical": True,
                "device": device,
                "provenance": {
                    "device_count": jax.device_count(),
                    "devices": sorted(
                        {d.platform for d in jax.devices()}
                    ),
                    "mesh": None,
                    "path": "serving/tier.py step loop (workers=0) over "
                    "AnalysisEngine; gang via ops/gramian."
                    "gang_gramian_blockwise, delta via ops/delta.py "
                    "rank-k correction",
                },
                "workload": {
                    "samples": n,
                    "variants": v,
                    "jobs": jobs,
                    "cohort_samples": cohort_n,
                    "references": refs,
                },
                "note": "results asserted bit-identical serial-vs-gang "
                "and cold-vs-delta before reporting; acceptance bars "
                "(delta >=10x, gang jobs/s > serial) tracked in "
                "BENCH_SERVE_r01.json",
                "timing": "rows are host values; drain loop timed "
                "submission-to-terminal",
            }
        )
    )


def pairhmm_bench():
    """BENCH_PAIRHMM=1: the read-level kernel leg (ROADMAP item 4).

    Measures the batched PairHMM forward pipeline end to end the way
    the product runs it — ``PairHmmDriver`` over a synthetic readset
    (stream reads → consensus vote → bucket/tile → batched forward) —
    and the raw kernel in isolation, reporting ``pairs/s`` for both
    with full backend provenance. Executables are warmed on the run's
    exact bucket shapes first, so the timed legs measure scoring, not
    first-call XLA compiles; per-pair results are asserted identical
    between the timed repeats before anything is reported.

    Knobs: BENCH_PAIRHMM_READS (default 2048), BENCH_PAIRHMM_READ_LEN
    (100), BENCH_PAIRHMM_REPEAT (3). One JSON line on stdout;
    BENCH_TRACE_OUT/BENCH_METRICS_OUT emit the telemetry artifacts
    (pairhmm.bucket/pairhmm.forward spans, pairhmm_pairs_total) that
    scripts/validate_trace.py schema-checks in CI.
    """
    import json as _json

    from spark_examples_tpu.genomics.fixtures import (
        FIXTURE_READSET_ID,
        synthetic_reads,
    )
    from spark_examples_tpu.models.pairhmm import PairHmmDriver
    from spark_examples_tpu.obs.session import TelemetrySession
    from spark_examples_tpu.ops.pairhmm import pairhmm_forward_batch
    from spark_examples_tpu.utils.config import PcaConfig

    device = _require_tpu()
    import jax

    n_reads = int(os.environ.get("BENCH_PAIRHMM_READS", 2048))
    read_len = int(os.environ.get("BENCH_PAIRHMM_READ_LEN", 100))
    repeat = int(os.environ.get("BENCH_PAIRHMM_REPEAT", 3))
    refs = "11:6880000:6920000"
    src = synthetic_reads(
        n_reads, references=refs, read_len=read_len, seed=7
    )
    conf = PcaConfig(
        references=refs,
        bases_per_partition=10_000,
        read_group_set_id=FIXTURE_READSET_ID,
    )
    outs = {
        "trace_out": os.environ.get("BENCH_TRACE_OUT") or None,
        "metrics_out": os.environ.get("BENCH_METRICS_OUT") or None,
        "manifest_out": os.environ.get("BENCH_MANIFEST_OUT") or None,
    }
    with TelemetrySession(
        **outs,
        command="bench-pairhmm",
        config={"reads": n_reads, "read_len": read_len},
    ):
        driver = PairHmmDriver(conf, src)
        rows_warm = driver.run_rows()  # compiles every bucket shape
        n_pairs = len(rows_warm)

        def run_pipeline():
            t0 = time.perf_counter()
            rows = driver.run_rows()
            return time.perf_counter() - t0, rows

        runs = [run_pipeline() for _ in range(max(1, repeat))]
        # EVERY repeat must match the warm rows — checking only the
        # fastest run would let a diverging slow repeat (exactly the
        # instability this assert exists to catch) ship a throughput
        # number under a false identity claim.
        for _, run_rows in runs:
            assert run_rows == rows_warm, (
                "per-pair log-likelihoods diverged between repeats — "
                "refusing to report throughput for unstable results"
            )
        t_pipe = min(t for t, _ in runs)
        # Raw kernel leg: one resident tile at the pipeline's dominant
        # bucket, host-readback barrier per dispatch.
        b = int(conf.pairhmm_batch)
        from spark_examples_tpu.ops.pairhmm import pairhmm_bucket

        r_b = pairhmm_bucket(read_len)
        h_b = pairhmm_bucket(read_len + 2 * conf.pairhmm_context)
        rng = np.random.default_rng(11)
        tile = (
            rng.integers(0, 4, (b, r_b)).astype(np.int8),
            rng.integers(10, 50, (b, r_b)).astype(np.int32),
            np.full(b, read_len, np.int32),
            rng.integers(0, 4, (b, h_b)).astype(np.int8),
            np.full(b, read_len + 2 * conf.pairhmm_context, np.int32),
        )

        def run_kernel():
            out = pairhmm_forward_batch(
                *tile, np.float32(45.0), np.float32(10.0)
            )
            np.asarray(out)  # host readback = the barrier
        run_kernel()  # warm
        t_kernel = _best(run_kernel, repeat=max(1, repeat))
    print(
        _json.dumps(
            {
                "metric": "pairhmm_pairs_per_sec",
                "value": round(n_pairs / t_pipe, 1),
                "unit": "pairs/s",
                "kernel_pairs_per_sec": round(b / t_kernel, 1),
                "pipeline_seconds": round(t_pipe, 4),
                "kernel_tile_seconds": round(t_kernel, 6),
                "pairs": n_pairs,
                "device": device,
                "provenance": {
                    "device_count": jax.device_count(),
                    "devices": sorted(
                        {d.platform for d in jax.devices()}
                    ),
                    "path": "models/pairhmm.PairHmmDriver.run_rows "
                    "(stream_reads -> consensus -> pow2 buckets -> "
                    "ops/pairhmm.pairhmm_forward_batch anti-diagonal "
                    "scan); kernel leg times one resident "
                    f"({b}, {r_b})x({b}, {h_b}) tile",
                },
                "workload": {
                    "reads": n_reads,
                    "read_len": read_len,
                    "references": refs,
                    "batch": b,
                    "bucket": f"r{r_b}xh{h_b}",
                },
                "note": "pipeline leg includes host prep (read "
                "streaming, consensus vote, tiling) on the "
                "completion-order feed; per-pair results asserted "
                "identical across repeats before reporting",
                "timing": "host-readback barrier per dispatch",
            }
        )
    )


def main():
    from spark_examples_tpu import obs
    from spark_examples_tpu.obs.session import TelemetrySession

    if os.environ.get("BENCH_PAIRHMM"):
        pairhmm_bench()
        return
    if os.environ.get("BENCH_COLD"):
        cold_start_bench()
        return
    if os.environ.get("BENCH_SERVE"):
        serving_bench()
        return
    if os.environ.get("BENCH_SCALE_OUT"):
        scale_out_sweep()
        return

    # The bench always collects its own telemetry (the per-stage
    # breakdown rides in the output JSON); files are written only when
    # the BENCH_*_OUT env vars ask for them.
    outs = {
        "trace_out": os.environ.get("BENCH_TRACE_OUT") or None,
        "metrics_out": os.environ.get("BENCH_METRICS_OUT") or None,
        "manifest_out": os.environ.get("BENCH_MANIFEST_OUT") or None,
    }
    with TelemetrySession(
        **outs,
        command="bench",
        config={
            "samples": N_SAMPLES,
            "block_v": BLOCK_V,
            "blocks": N_BLOCKS,
        },
    ) as session:
        _bench_body(session)


def _bench_body(session):
    device = _require_tpu()
    from spark_examples_tpu import obs

    peaks = _peaks(device)
    x = make_cohort()
    times, mode_used, coords_tpu = tpu_phase_times(x)
    t_tpu = times[mode_used]

    import jax

    from spark_examples_tpu.ops.gramian import pack_indicator_block
    from spark_examples_tpu.ops.pcoa import normalize_eigvec_signs

    x_packed = pack_indicator_block(x)
    with obs.span("measure_link"):
        t_floor, link_bw = measure_link(x_packed)
    _log(
        f"bench: sync floor {t_floor * 1e3:.1f}ms, link "
        f"{link_bw / 1e6:.0f} MB/s"
    )

    with obs.span("cpu_baseline"):
        t_cpu, coords_ref = cpu_reference_time(x)
    parity = float(
        np.abs(
            normalize_eigvec_signs(np.asarray(coords_tpu, np.float64))
            - normalize_eigvec_signs(np.asarray(coords_ref, np.float64))
        ).max()
    )
    _log(f"bench: parity vs f64 MLlib-literal golden {parity:.2e}")
    # `not (parity <= bar)` rather than `parity > bar`: NaN coordinates
    # must FAIL the gate, not sail through a False comparison.
    if not (parity <= 1e-4):
        # A performance number with wrong coordinates is not a result.
        _log(
            "bench: FATAL — coordinate parity "
            f"{parity:.2e} exceeds the 1e-4 bar on a real backend; "
            "refusing to report a speedup for incorrect output"
        )
        sys.exit(1)

    flops = 2.0 * N_SAMPLES * N_SAMPLES * N_VARIANTS  # Gramian MACs×2
    bytes_moved = x_packed.nbytes + N_SAMPLES * NUM_PC * 4
    t_model, model_terms = overlapped_roofline(
        bytes_moved, link_bw, t_floor, flops, peaks["int8_ops"]
    )
    with obs.span("ingest_probe"):
        ingest = measure_ingest(x)
    with obs.span("compute_bound_probe"):
        compute_bound = measure_compute_bound(peaks["bf16_flops"])
    _log(
        f"bench: compute-bound probe {compute_bound['tflops_effective']}"
        " TFLOP/s effective"
    )
    value = N_SAMPLES * N_SAMPLES * N_VARIANTS / t_tpu
    print(
        json.dumps(
            {
                "metric": "pcoa_samples2_variants_per_sec",
                "value": value,
                "unit": "samples^2*variants/s",
                "vs_baseline": t_cpu / t_tpu,
                "device": device,
                "modes_measured": sorted(times),
                "mode_used": mode_used,
                "mode_times_s": {k: round(v, 4) for k, v in times.items()},
                # Per-stage wall-clock decomposition from the telemetry
                # tracer (warm vs steady per mode, link probe, CPU
                # baseline) — BENCH rounds diff stages, not one number.
                "stages": {
                    k: round(v, 4)
                    for k, v in sorted(
                        session.tracer.stage_seconds().items()
                    )
                },
                "product_invocation": {
                    k: PRODUCT_INVOCATION[k] for k in sorted(times)
                },
                "workload": {
                    "samples": N_SAMPLES,
                    "variants": N_VARIANTS,
                    "cohort": "3-subpopulation structured, ~10% density",
                },
                "parity_max_abs_delta_vs_f64_golden": parity,
                "parity_ok_1e4": parity <= 1e-4,
                # Roofline: bytes/bandwidth + one sync roundtrip
                # against device compute at the published peak. The model is the OVERLAPPED (double-buffered) lower
                # bound, so fraction <= 1 by construction and a
                # fraction drifting down flags a real regression
                # (round-5 weak #3: the serial model was beaten at
                # 1.046 and could flag nothing).
                "roofline": {
                    "bytes_moved": bytes_moved,
                    "link_bw_bytes_per_s": round(link_bw),
                    "sync_floor_s": round(t_floor, 4),
                    "gramian_flops": flops,
                    "peaks": peaks,
                    "model_time_s": round(t_model, 4),
                    "model_terms": model_terms,
                    "achieved_time_s": round(t_tpu, 4),
                    "roofline_fraction": round(t_model / t_tpu, 3),
                    "mfu_vs_int8_peak": round(
                        flops / t_tpu / peaks["int8_ops"], 6
                    ),
                },
                # Host block-production throughput (the round-5 ingest
                # wall): best-mode blocks/sec headline + per-mode build
                # time, so BENCH rounds track the ingest stage too.
                "ingest_blocks_per_sec": ingest["blocks_per_sec"][
                    ingest["mode_best"]
                ],
                "ingest": ingest,
                # Compute-bound utilization, promoted from a side
                # artifact to a first-class field (round-5 weak #3).
                "compute_bound_tflops": compute_bound[
                    "tflops_effective"
                ],
                "compute_bound": compute_bound,
                "timing": "host-readback barrier",
                "baseline_accum": "measured in full",
                "baseline_eig": "measured in full (f64 LAPACK)",
                "baseline_spark_note": "pyspark local[4] anchor impossible "
                "in this image (no JVM, no pip); numpy emulation follows "
                "variants_pca.py:54-121 literally (BASELINE.md)",
            }
        )
    )


if __name__ == "__main__":
    main()
