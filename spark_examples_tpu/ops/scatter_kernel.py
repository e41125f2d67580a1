"""Pallas scatter-accumulate kernel for the OOB-drop tile update.

The sparse engine's scatter (``ops/sparse.scatter_pairs_chunked``) is a
chunked ``lax.scan`` over XLA scatter-adds: correct and portable, but on
TPU every update serializes through the scatter unit while the
accumulating G tile bounces through HBM once per chunk. This module is
the fused alternative in the spirit of the blocked genotype-matrix
kernels of *Fast PCA of genotype matrices in Julia* (arxiv 1808.03374):
reformulate each variant's contribution as a rank-1 outer product of
one-hot *count* vectors,

    ΔG = Σ_v r_v · c_vᵀ,   r_v[t] = |{a : row_idx[v,a] = t}|,

so a chunk of C variants becomes ONE (BR, C) × (C, TC) MXU matmul with
the accumulating tile block held VMEM-resident across every carrier
chunk (the grid revisits the same output block over the chunk axis —
the tile leaves VMEM once, at the end). Out-of-bounds indices (the
carrier pad sentinel, out-of-tile carriers) match no one-hot lane and
drop exactly like the scatter's ``mode="drop"``; duplicate carriers
count multiply, exactly like scatter-add duplicate semantics. Every
update is an exact small-integer count in float32, so the result is
**bit-identical** to the scan path (pinned by tests/test_scatter_kernel).

Selection (resolved OUTSIDE any trace — the callers thread the decision
in as a static arg):

- ``SPARK_EXAMPLES_TPU_SCATTER_KERNEL=0`` — kill switch, scan always
  (the CI kernel-fallback leg runs the whole scatter suite this way);
- ``SPARK_EXAMPLES_TPU_SCATTER_KERNEL=interpret`` — force the Pallas
  kernel in interpreter mode (runs on CPU; how the tests pin
  bit-identity without a TPU);
- unset / ``1`` — auto: the compiled kernel on Mosaic-capable backends
  (TPU) when the tile geometry fits the VMEM budget
  (``SPARK_EXAMPLES_TPU_SCATTER_KERNEL_VMEM`` bytes, default 8 MiB),
  the scan path everywhere else — CPU/GPU simulations keep their exact
  historical executable.

Per window, host side (:func:`window_scatter_path`), a single-device
window on a TPU that the stream would scatter on the scan or the
compiled kernel takes ``"mxu"`` instead when its carrier bucket is at most
:data:`MXU_MAX_CARRIER_BUCKET`: the window's (N, V_pad) int8
carrier-count panel is built on the device and contracted on the int8
MXU. A host-local mesh tile's window takes ``"mxu-tile"`` when no
carrier count passes int8: each tile builds the panels of its row and
column ranges from the window's carrier list and adds their product.
The kill switch keeps the scan for every window.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MXU_MAX_CARRIER_BUCKET",
    "kernel_block_rows",
    "resolve_scatter_path",
    "scatter_pairs_kernel",
    "window_scatter_path",
]

# float32 hardware tiling on TPU: (8, 128) min tile — kernel eligibility
# requires the G tile to divide into lane-aligned blocks.
_SUBLANE = 8
_LANE = 128

_DEFAULT_VMEM_BUDGET = 8 << 20

# The MXU update holds per-(sample, variant) carrier counts in int8: a
# count is at most the carrier bucket, so the bucket may not pass 127.
MXU_MAX_CARRIER_BUCKET = 127


def _vmem_budget() -> int:
    raw = os.environ.get("SPARK_EXAMPLES_TPU_SCATTER_KERNEL_VMEM", "")
    try:
        return int(raw) if raw else _DEFAULT_VMEM_BUDGET
    except ValueError:
        return _DEFAULT_VMEM_BUDGET


def _chunk_variants() -> int:
    from spark_examples_tpu.ops.sparse import SCATTER_CHUNK_VARIANTS

    return SCATTER_CHUNK_VARIANTS


def kernel_block_rows(t_r: int, t_c: int, k: int = 0) -> Optional[int]:
    """Largest VMEM-fitting row-block size for a (t_r, t_c) f32 tile.

    The kernel holds per grid step: the g input block + output block,
    each double-buffered by the Pallas pipeline (4·BR·TC·4 B); the
    chunk's one-hot count scratch (C·(BR+TC)·4 B); and the two (K, C)
    int32 index blocks, double-buffered (4·K·C·4 B) — NOT small at
    biobank carrier buckets, so ``k`` must be charged when known (the
    host dispatch knows it per window: :func:`window_scatter_path`;
    the resolve-time heuristic passes 0). Returns a sublane-aligned
    divisor of ``t_r``, or ``None`` when even the minimum 8-row block
    cannot fit.
    """
    c = _chunk_variants()
    # The (TC, C) col-count scratch + the double-buffered index blocks.
    budget = _vmem_budget() - c * t_c * 4 - 4 * c * k * 4
    if budget <= 0:
        return None
    cap = budget // (4 * t_c * 4 + c * 4)  # g in+out blocks + row counts
    cap = min(t_r, (cap // _SUBLANE) * _SUBLANE)
    br = cap
    while br >= _SUBLANE:
        if t_r % br == 0:
            return br
        br -= _SUBLANE
    return None


def _kernel_eligible(tile_shape: Tuple[int, int], dtype) -> bool:
    t_r, t_c = int(tile_shape[0]), int(tile_shape[1])
    if np.dtype(dtype) != np.dtype(np.float32):
        # The one-hot count formulation is argued exact for f32 (the
        # engine's accumulator dtype); other dtypes keep the scan path.
        return False
    if t_r % _SUBLANE or t_c % _LANE:
        return False
    return kernel_block_rows(t_r, t_c) is not None


_KILL_SWITCH = ("0", "off", "scan")


def _scatter_mode() -> str:
    return (
        os.environ.get("SPARK_EXAMPLES_TPU_SCATTER_KERNEL", "")
        .strip()
        .lower()
    )


def resolve_scatter_path(tile_shape: Tuple[int, int], dtype=np.float32):
    """``"scan" | "pallas" | "interpret"`` for one tile geometry.

    Resolved OUTSIDE any jit trace (same discipline as
    ``resolve_gramian_compute_dtype``): the callers cache executables
    per (geometry, path), so the env switch takes effect per
    accumulation stream, never mid-trace.
    """
    mode = _scatter_mode()
    if mode in _KILL_SWITCH:
        return "scan"
    if not _kernel_eligible(tile_shape, dtype):
        return "scan"
    if mode == "interpret":
        return "interpret"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "scan"


def window_scatter_path(
    path: str,
    tile_shape: Tuple[int, int],
    k: int,
    single_device: bool = False,
    tile_counts_fit_int8: Optional[bool] = None,
) -> str:
    """The path ONE window's scatter takes, counted — host side, per window.

    The stream's ``path`` (:func:`resolve_scatter_path`) cannot see the
    carrier bucket ``k``, which varies per window. The single-device
    accumulator passes ``single_device``: on a TPU, a window its stream
    would scatter on the scan or the compiled kernel takes ``"mxu"``
    when ``k`` is at most :data:`MXU_MAX_CARRIER_BUCKET`, unless the
    kill switch is set (on a TPU v5e the MXU update is 5–200× faster
    than the kernel at N = 1,024–6,144, and 185× faster than the scan at
    N = 3,202, k = 64). The host-local mesh accumulator passes
    ``tile_counts_fit_int8``, the host's check that no carrier repeats
    past int8 within a site
    (:func:`spark_examples_tpu.ops.sparse.carrier_counts_fit_int8`): on
    a TPU such a window takes ``"mxu-tile"``, each tile's row and
    column carrier-count panels built from the window's carrier list
    and contracted on the int8 MXU, whatever ``k``.
    Otherwise a kernel path whose (K, C)
    index blocks push a grid step over the VMEM budget takes the chunked
    scan instead (bit-identical), and that fall is counted as
    ``path="fallback-scan"`` in ``sparse_scatter_path_windows_total`` —
    never decided silently inside a trace.
    """
    taken = label = path
    mxu_ok = (
        path in ("scan", "pallas")
        and _scatter_mode() not in _KILL_SWITCH
        and jax.default_backend() == "tpu"
    )
    if mxu_ok and single_device and k <= MXU_MAX_CARRIER_BUCKET:
        taken = label = "mxu"
    elif mxu_ok and tile_counts_fit_int8:
        taken = label = "mxu-tile"
    elif path != "scan" and kernel_block_rows(*tile_shape, k) is None:
        taken, label = "scan", "fallback-scan"
    from spark_examples_tpu import obs

    obs.get_registry().counter(
        "sparse_scatter_path_windows_total",
        "Scatter-route windows by the implementation they took (mxu = "
        "device-built carrier-count panel on the int8 MXU; mxu-tile = a "
        "mesh tile's row and column panels on the int8 MXU; "
        "fallback-scan = a kernel stream's window over the VMEM budget)",
    ).labels(path=label).inc()
    return taken


def _scatter_kernel_body(br: int, t_c: int, k: int, c: int):
    """Kernel closure for fixed block geometry (all shapes static).

    Index blocks arrive transposed, ``(K, C)``: carrier ``a`` of the
    chunk is one sublane row (a dynamic sublane slice Mosaic lowers),
    broadcast against a sample iota laid out along sublanes, so both
    one-hot count panels keep the chunk's C variants on the lane axis
    and the outer-product sum is an A·Bᵀ contraction over lanes.
    """

    def kernel(row_ref, col_ref, g_ref, out_ref, r_cnt_ref, c_cnt_ref):
        from jax.experimental import pallas as pl

        j = pl.program_id(1)  # carrier-chunk position (innermost)

        @pl.when(j == 0)
        def _():
            # First chunk of this row block: seed the VMEM-resident
            # accumulator from the incoming tile block; later chunks
            # revisit the same block and accumulate in place.
            out_ref[:] = g_ref[:]

        base = pl.program_id(0) * br
        r_cnt_ref[:] = jnp.zeros((br, c), jnp.float32)
        c_cnt_ref[:] = jnp.zeros((t_c, c), jnp.float32)

        def body(a, carry):
            r = row_ref[pl.ds(a, 1), :]  # (1, C) int32, OOB >= t_r
            cc = col_ref[pl.ds(a, 1), :]
            row_iota = (
                jax.lax.broadcasted_iota(jnp.int32, (br, c), 0) + base
            )
            col_iota = jax.lax.broadcasted_iota(jnp.int32, (t_c, c), 0)
            r_cnt_ref[:] += (row_iota == r).astype(jnp.float32)
            c_cnt_ref[:] += (col_iota == cc).astype(jnp.float32)
            return carry

        jax.lax.fori_loop(0, k, body, 0)
        # Σ_v r_v · c_vᵀ over the chunk: one MXU contraction — counts
        # are exact small integers in f32, so the add is exact.
        # precision=HIGHEST: the default matmul precision routes f32
        # operands through bf16 multiplies on TPU, which would round
        # duplicate-carrier counts above 256 and break the
        # bit-identity contract exactly on the backend that
        # auto-selects this kernel.
        out_ref[:] += jax.lax.dot_general(
            r_cnt_ref[:],
            c_cnt_ref[:],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    return kernel


def scatter_pairs_kernel(g, row_idx, col_idx, interpret: bool = False):
    """``g[row_idx[v,a], col_idx[v,b]] += 1`` — the Pallas formulation.

    Drop-in for :func:`spark_examples_tpu.ops.sparse.scatter_pairs_chunked`
    (same operands, same OOB-drop and duplicate semantics, bit-identical
    result); callers must have resolved eligibility via
    :func:`resolve_scatter_path` and, per window, the carrier bucket via
    :func:`window_scatter_path` first — a geometry that cannot stage is
    an error here, not a quiet change of implementation.
    Traceable under jit/shard_map.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_r, t_c = g.shape
    v_pad, k = row_idx.shape
    c = _chunk_variants()
    br = kernel_block_rows(t_r, t_c, k)
    if br is None:
        raise ValueError(
            f"scatter kernel cannot stage a ({t_r}, {t_c}) tile with "
            f"carrier bucket {k} in {_vmem_budget()} B of VMEM; the "
            "dispatch must route this window to the scan path"
        )
    grid = (t_r // br, v_pad // c)
    return pl.pallas_call(
        _scatter_kernel_body(br, t_c, k, c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, c), lambda i, j: (0, j)),
            pl.BlockSpec((k, c), lambda i, j: (0, j)),
            pl.BlockSpec((br, t_c), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, t_c), lambda i, j: (i, 0)),
        # Inside shard_map the result varies over the same mesh axes as
        # the tile it accumulates into.
        out_shape=jax.ShapeDtypeStruct(
            (t_r, t_c), g.dtype, vma=jax.typeof(g).vma
        ),
        scratch_shapes=[
            pltpu.VMEM((br, c), jnp.float32),
            pltpu.VMEM((t_c, c), jnp.float32),
        ],
        interpret=interpret,
    )(row_idx.T, col_idx.T, g)
