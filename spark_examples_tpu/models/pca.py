"""The PCoA pipeline driver — the north-star component.

TPU re-architecture of ``VariantsPcaDriver`` (``VariantsPca.scala:36-246``)
with the same public stage surface — get_data / filter_dataset / get_calls /
get_similarity_matrix / compute_pca / emit_result / report_io_stats — but a
fundamentally different execution model:

- Spark RDD lineage → plain host generators (ingest is IO-bound; no shuffle);
- per-task Breeze N×N accumulation + reduceByKey shuffle of N² entries →
  ``G += X_blk @ X_blk.T`` on the MXU, variant axis streamed, G resident in
  HBM (``VariantsPca.scala:170-191`` becomes
  :func:`spark_examples_tpu.ops.gramian_blockwise`);
- driver collect/broadcast row sums + per-row centering
  (``VariantsPca.scala:198-223``) → one fused ``double_center`` jit;
- MLlib RowMatrix.computePrincipalComponents (eig on the driver JVM,
  ``VariantsPca.scala:225-226``) → ``jnp.linalg.eigh`` on device (or host
  float64 with ``--precise``), using the |λ|-ordering equivalence documented
  in :mod:`spark_examples_tpu.ops.pcoa`.

Output is byte-format compatible with ``emitResult``
(``VariantsPca.scala:233-246``): stdout ``name\tdataset\tpc1\tpc2`` sorted by
name; ``--output-path`` writes ``<path>-pca.tsv`` lines
``name\tpc1\tpc2\tdataset``.
"""

from __future__ import annotations

import os
import sys
from typing import Iterable, Iterator, List, Sequence, Tuple

import jax
import numpy as np

from spark_examples_tpu.arrays.blocks import blocks_from_calls
from spark_examples_tpu.genomics.callsets import CallsetIndex
from spark_examples_tpu.genomics.datasets import af_filter, calls_stream
from spark_examples_tpu.genomics.shards import SexChromosomeFilter
from spark_examples_tpu.genomics.types import Variant
from spark_examples_tpu.ops import (
    gramian_blockwise,
    mllib_principal_components_reference,
    pcoa,
)
from spark_examples_tpu.utils.config import PCA_MODES, PcaConfig

__all__ = ["PCA_MODES", "VariantsPcaDriver"]


def _contig_runs_unique(shards) -> bool:
    """True when the manifest presents each contig as one contiguous run —
    the precondition for contig-partitioned (bounded-memory) joins."""
    seen = set()
    last = None
    for s in shards:
        if s.contig != last:
            if s.contig in seen:
                return False
            seen.add(s.contig)
            last = s.contig
    return True


class VariantsPcaDriver:
    def __init__(self, conf: PcaConfig, source, mesh=None, index=None):
        if conf.num_pc < 1:
            # Validate before any ingest work — failing in stage 5 would
            # waste the whole (potentially hours-long) Gramian pass.
            raise ValueError(f"--num-pc must be >= 1, got {conf.num_pc}")
        if conf.elastic_checkpoint and not conf.checkpoint_dir:
            # A checkpoint flag that silently does nothing loses the user
            # hours of presumed-checkpointed work — refuse up front.
            # (Multi-dataset preconditions — fused keyed source, unique
            # contig runs — are validated in _checkpointed_elastic,
            # still before any ingest.)
            raise ValueError(
                "--elastic-checkpoint requires --checkpoint-dir"
            )
        if getattr(conf, "ingest_order", "auto") not in (
            "auto",
            "manifest",
            "completion",
        ):
            # argparse choices only guard the CLI (same reasoning as
            # pca_mode below).
            raise ValueError(
                f"ingest_order must be 'auto', 'manifest', or "
                f"'completion'; got {conf.ingest_order!r}"
            )
        if getattr(conf, "prefetch_depth", 2) < 1:
            # A zero/negative staging depth would deadlock the bounded
            # feed queue — refuse before any ingest work.
            raise ValueError(
                f"--prefetch-depth must be >= 1, got {conf.prefetch_depth}"
            )
        if conf.ingest_workers < 0:
            raise ValueError(
                f"--ingest-workers must be >= 1 (or 0 = auto), got "
                f"{conf.ingest_workers}"
            )
        if conf.pca_mode not in PCA_MODES:
            # argparse choices only guard the CLI; a programmatic typo
            # ('streaming', 'Stream') would otherwise silently fall
            # through to the auto gate. The allowed set and this error
            # message both derive from the ONE registry
            # (utils.config.PCA_MODES) — a sync test pins them.
            allowed = ", ".join(repr(m) for m in PCA_MODES)
            raise ValueError(
                f"pca_mode must be one of {allowed}; got "
                f"{conf.pca_mode!r}"
            )
        if conf.pca_mode == "sparse" and conf.checkpoint_dir:
            # Snapshot digests cut at manifest positions; the sparse
            # accumulator's window stream has no checkpoint grid yet.
            # Refuse before ingest, not after hours of it.
            raise ValueError(
                "--pca-mode sparse does not compose with checkpointed "
                "ingest yet; drop --checkpoint-dir or use --pca-mode "
                "auto/stream"
            )
        if conf.pca_mode == "sketch" and conf.checkpoint_dir:
            # The sketch panel has no snapshot grid (and a resumed
            # partial panel would silently double-count windows).
            raise ValueError(
                "--pca-mode sketch does not compose with checkpointed "
                "ingest; drop --checkpoint-dir or use --pca-mode auto"
            )
        if conf.pca_mode == "sketch" and conf.precise:
            # --precise is definitionally the host-f64 EXACT route; the
            # sketch engine is approximate by contract. Refuse the
            # contradiction rather than silently demote either flag.
            raise ValueError(
                "--pca-mode sketch is the randomized approximate "
                "engine and cannot honor --precise; drop one"
            )
        if getattr(conf, "sketch_oversample", 8) < 1:
            raise ValueError(
                "--sketch-oversample must be >= 1 (the panel needs a "
                "value past k for the spectral-gap check), got "
                f"{conf.sketch_oversample}"
            )
        if getattr(conf, "sketch_power_iters", 0) < 0:
            raise ValueError(
                "--sketch-power-iters must be >= 0, got "
                f"{conf.sketch_power_iters}"
            )
        if getattr(conf, "sparse_density_threshold", 0.02) < 0:
            raise ValueError(
                "--sparse-density-threshold must be >= 0, got "
                f"{conf.sparse_density_threshold}"
            )
        if getattr(conf, "pod_pipeline_depth", 2) < 0:
            raise ValueError(
                "--pod-pipeline-depth must be >= 0 (0 = inline "
                f"lockstep), got {conf.pod_pipeline_depth}"
            )
        if getattr(conf, "pod_coalesce_variants", 256) < 0:
            raise ValueError(
                "--pod-coalesce-variants must be >= 0 (0 disables "
                f"coalescing), got {conf.pod_coalesce_variants}"
            )
        if mesh is None and conf.mesh_shape:
            # ``--mesh-shape`` is this class's to honor: every caller that
            # builds a driver from a config (the CLI, the benchmark) gets
            # the configured mesh. Meshes over the same devices and axes
            # compare equal, so a fresh driver per analysis hits the same
            # executables. An explicit ``mesh=`` (the serving engine's)
            # wins.
            from spark_examples_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(conf.mesh_shape)
        if conf.pca_mode == "fused" and (
            conf.precise or mesh is not None or jax.process_count() > 1
        ):
            # Fail before ingest, not after hours of Gramian work: the
            # fused finish is a single-device program (one replicated G,
            # one host readback) and --precise is definitionally the
            # host-f64 route.
            raise ValueError(
                "--pca-mode fused requires a single-process, meshless, "
                "non---precise run (use --pca-mode auto to fall back "
                "automatically)"
            )
        # `samples is not None` rather than truthiness: an EXPLICITLY
        # empty include list is a contradictory cohort and must hit the
        # loud "leaves no samples" error, never silently run the full
        # cohort. (An empty exclude list excludes nothing — that IS the
        # unrestricted cohort.)
        restricted = getattr(conf, "samples", None) is not None or bool(
            getattr(conf, "exclude_samples", None)
        )
        if restricted and conf.checkpoint_dir:
            # Snapshot digests don't cover the sample restriction yet,
            # and a restricted resume against an unrestricted snapshot
            # would be silently wrong — refuse before ingest.
            raise ValueError(
                "--samples/--exclude-samples do not compose with "
                "checkpointed ingest; drop --checkpoint-dir"
            )
        if restricted and mesh is not None:
            # Mesh tiling/sample-range contracts are full-frame; the
            # serving tier that drives restriction is meshless.
            raise ValueError(
                "--samples/--exclude-samples require a meshless run "
                "(drop --mesh-shape)"
            )
        self.conf = conf
        self.source = source
        self.mesh = mesh
        # A pre-built index makes the driver cheap to construct per job:
        # the serving engine (serving/engine.py) shares ONE immutable
        # CallsetIndex across concurrent jobs over the same cohort
        # instead of re-listing callsets per submission.
        self.index = (
            index
            if index is not None
            else CallsetIndex.from_source(source, conf.variant_set_ids)
        )
        # The COHORT frame: ingest always extracts in the full index
        # frame (unknown callsets stay a hard error there), and a
        # sample restriction remaps/filters carriers at the window
        # boundary — `self.cohort` is what the Gramian, the finish, and
        # emission are sized by; `_sample_remap` (full dense index →
        # cohort index, -1 drops) is the one filter array.
        if restricted:
            self.cohort, self._sample_remap = self.index.restricted(
                getattr(conf, "samples", None),
                getattr(conf, "exclude_samples", None),
            )
        else:
            self.cohort, self._sample_remap = self.index, None
        self._pin_g_jit = None  # compiled-once G-resharding (pod snapshots)
        self._speculated_shards = 0  # straggler duplicates launched

    def _watchdog(self):
        """Collective fail-stop guard (utils/watchdog.py), armed only for
        multi-process runs — a lone process has no peer to lose and must
        never be shot by a timer. Checkpointed pod ingest arms per ROUND;
        every other pod collective phase (uncheckpointed ingest, DCN
        merge, distributed eig) is armed as one phase here, so the flag
        is never a silent no-op."""
        from spark_examples_tpu.utils.watchdog import CollectiveWatchdog

        timeout = self.conf.collective_timeout
        return CollectiveWatchdog(
            timeout if jax.process_count() > 1 else None
        )

    # -- stage 1: ingest -----------------------------------------------------

    def get_data(self) -> List[Iterator[Variant]]:
        """One lazy variant stream per configured variantset.

        The analog of ``VariantsCommon.data`` (VariantsCommon.scala:52-66):
        nothing is fetched until the Gramian pass consumes the streams.
        Under multi-host each process ingests a round-robin slice of the
        manifest; partial Gramians merge in get_similarity_matrix.
        """
        shards = self._manifest()
        # When the manifest visits each contig exactly once (one contiguous
        # run — true for --all-references and any non-repeating
        # --references), multi-dataset joins may partition their state by
        # contig instead of holding the whole cohort's identities.
        self._contig_runs_unique = _contig_runs_unique(shards)

        def stream(vsid: str) -> Iterator[Variant]:
            for shard in shards:
                yield from self.source.stream_variants(vsid, shard)

        return [stream(vsid) for vsid in self.conf.variant_set_ids]

    @staticmethod
    def _host_shards(shards):
        """Round-robin manifest slice for this process (DP across hosts)."""
        if jax.process_count() > 1:
            return shards[jax.process_index() :: jax.process_count()]
        return shards

    def _global_manifest(self):
        """The full, unsliced shard manifest — the ONE place the
        partitioner parameters live, so fused/staged/checkpointed/elastic
        ingest can never disagree on what they ingest."""
        return self.conf.shards(
            all_references=self.conf.all_references,
            sex_filter=SexChromosomeFilter.EXCLUDE_XY,
        )

    def _manifest(self):
        """This process's shard manifest slice."""
        return self._host_shards(self._global_manifest())

    # -- stage 2: filters ----------------------------------------------------

    def filter_dataset(self, data: Iterable[Variant]) -> Iterator[Variant]:
        if self.conf.min_allele_frequency is not None:
            print(f"Min allele frequency {self.conf.min_allele_frequency}.")
        return af_filter(data, self.conf.min_allele_frequency)

    # -- stage 3: calls ------------------------------------------------------

    def get_calls(
        self, streams: Sequence[Iterable[Variant]]
    ) -> Iterator[List[int]]:
        """Per-variant carrying-sample index lists (the RDD[Seq[Int]]
        interface at VariantsPca.scala:153-168)."""
        if self.conf.debug_datasets:
            streams = [self._debug_wrap(s) for s in streams]
        return calls_stream(
            list(streams),
            self.index.indexes,
            contig_runs_unique=getattr(self, "_contig_runs_unique", False),
        )

    def _fused_ingest_possible(self) -> bool:
        """The fast path fuses ingest → AF filter → call extraction when
        nothing needs full Variant/Call records: single dataset (no
        identity join), no --debug-datasets printing, and a source that
        implements stream_carrying."""
        return (
            len(self.conf.variant_set_ids) == 1
            and not self.conf.debug_datasets
            and hasattr(self.source, "stream_carrying")
        )

    def get_calls_fused(self) -> Iterator[List[int]]:
        """Fused single-dataset ingest: shards → carrying index lists.

        Same observable behavior as get_data → filter_dataset → get_calls
        (verified by parity tests) minus the per-call object
        materialization that dominates host ingest at chr20+ scale.
        """
        vsid = self.conf.variant_set_ids[0]
        shards = self._manifest()
        if self.conf.min_allele_frequency is not None:
            print(
                f"Min allele frequency {self.conf.min_allele_frequency}."
            )
        yield from self._parallel_shard_calls(vsid, shards)

    def _ingest_workers(self) -> int:
        """--ingest-workers; auto = core count capped at 16 (1 → serial).

        The cap bounds peak host memory: each in-flight worker holds one
        shard's materialized call lists (ordered_parallel_map keeps
        workers+2 results buffered), so an uncapped auto on a 96-core TPU
        VM could hold ~100 shards of call data at once. Users who have
        the RAM opt into more with an explicit --ingest-workers.
        """
        if self.conf.ingest_workers:
            return self.conf.ingest_workers
        return min(os.cpu_count() or 1, 16)

    def _block_builder_workers(self) -> int:
        """Builder threads for the packed-block production stage
        (``--ingest-workers``; auto = min(4, cores), 1 → serial).

        A separate, smaller auto cap than shard extraction: each builder
        holds exactly one packed block (N × ⌈Vb/8⌉ bytes, 8× less than
        an extraction worker's call lists) but the stage is pure memory
        bandwidth — past ~4 threads the scatter saturates the memory
        controller, not the cores. On a single-core host auto = 1, the
        serial in-order path, so the CLI default is byte-identical to
        the historical pipeline there.
        """
        if self.conf.ingest_workers:
            return self.conf.ingest_workers
        return min(os.cpu_count() or 1, 4)

    def _build_attempt(self, thunk, key: str):
        """Run one packed-block build under the resilience layer — the
        ``ingest.build`` fault seam (a builder worker dying mid-block)
        plus up to ``--shard-retries`` attempts. Sound because the build
        is a pure function of its already-sliced window: a retry yields
        a byte-identical block, so a worker death can change wall-clock,
        never G — and a block is either built or the run fails loudly
        (no silent drop). Default (1 attempt, no plan): zero overhead.
        """
        from spark_examples_tpu import resilience
        from spark_examples_tpu.resilience import faults

        retries = max(1, getattr(self.conf, "shard_retries", 1))
        if retries <= 1 and faults.current_plan() is None:
            return thunk()

        def attempt():
            faults.inject("ingest.build", key=key)
            return thunk()

        return resilience.call_with_retry(
            attempt,
            resilience.RetryPolicy(
                max_attempts=retries,
                base_delay=0.05,
                deadline=getattr(self.conf, "shard_retry_deadline", None),
            ),
            resilience.classify_ingest,
            transport="ingest",
            method="build",
        )

    def _shard_attempt(self, shard, fn):
        """Run one idempotent shard extraction under the resilience
        layer: up to ``--shard-retries`` attempts, each drawing down the
        per-shard ``--shard-retry-deadline`` budget, with the
        ``ingest.shard`` fault-plane seam in front (worker death = an
        injected error, a slow lane = an injected stall). Re-execution
        is sound because the manifest is deterministic and per-shard
        ingest idempotent (STRICT boundaries) — a retried shard yields
        byte-identical call lists, so results never change, only
        wall-clock. Default (1 attempt, no plan) adds zero overhead."""
        from spark_examples_tpu import resilience
        from spark_examples_tpu.resilience import faults

        retries = max(1, getattr(self.conf, "shard_retries", 1))
        if retries <= 1 and faults.current_plan() is None:
            return fn()

        def attempt():
            faults.inject("ingest.shard", key=str(shard))
            return fn()

        return resilience.call_with_retry(
            attempt,
            resilience.RetryPolicy(
                max_attempts=retries,
                base_delay=0.05,
                deadline=getattr(self.conf, "shard_retry_deadline", None),
            ),
            resilience.classify_ingest,
            transport="ingest",
            method="shard",
        )

    def _parallel_shard_calls(
        self, vsid: str, shards, stream_method=None, workers=None
    ):
        """Per-shard extraction lists in EXACT manifest order, produced
        by N workers (utils/concurrency.py): wall-clock parallelism with
        bit-identical results — block packing and accumulation order
        never change. Serial when workers == 1. ``stream_method``
        defaults to the single-dataset fused stream; the keyed
        multi-dataset path passes its own."""
        from spark_examples_tpu.utils.concurrency import (
            ordered_parallel_map,
        )

        method = stream_method or self.source.stream_carrying

        def extract(shard):
            return self._shard_attempt(
                shard,
                lambda: list(
                    method(
                        vsid,
                        shard,
                        self.index.indexes,
                        self.conf.min_allele_frequency,
                    )
                ),
            )

        def note_speculation(shard):
            self._speculated_shards += 1
            print(
                f"Speculating straggler shard {shard} "
                "(duplicate attempt launched).",
                file=sys.stderr,
            )
            from spark_examples_tpu import obs

            obs.instant(
                "speculative_shard_attempt", scope="p", shard=str(shard)
            )

        for calls in ordered_parallel_map(
            extract,
            shards,
            workers or self._ingest_workers(),
            speculate=self.conf.speculative_ingest,
            on_speculate=note_speculation,
        ):
            yield from calls

    def _fused_csr_possible(self) -> bool:
        """CSR-direct ingest: the fused single-dataset preconditions plus
        a source that can serve whole shards as (indices, offsets) pairs
        (the JSONL sidecar tier). Skipped when speculation is on — the
        straggler race re-executes extractions, which is pointless for
        the sidecar's in-memory array slicing."""
        return (
            self._fused_ingest_possible()
            and hasattr(self.source, "stream_carrying_csr")
            and not self.conf.speculative_ingest
        )

    def _cold_stream_active(self) -> bool:
        """Is the source streaming a COLD remote cohort from the wire
        while its mirror downloads write-through in the background?
        (Sources without the concept — local sidecars, fixtures —
        answer False.)"""
        probe = getattr(self.source, "cold_stream_active", None)
        return bool(probe()) if probe is not None else False

    def get_csr_fused(self):
        """Fused single-dataset ingest as per-shard CSR pairs — the
        vectorized twin of :meth:`get_calls_fused` (same filters and
        stats; ~85% of warm host wall-clock at all-autosomes scale was
        the per-variant list round-trip this skips).

        ``--ingest-order completion`` feeds pairs in SHARD-COMPLETION
        order instead of manifest order: the fetch+decode workers (the
        remote binary-frame tier's pool) hand each shard to the device
        accumulator the moment it lands, so one slow shard never
        head-of-line-blocks the stream. Safe because the Gramian
        accumulates exact integer co-occurrence counts (every count sits
        far below 2^24, the f32 exact-integer bound), so G is
        bit-identical under any arrival order — pinned by test. Block
        COMPOSITION differs, which is why checkpointed modes (snapshot
        digests cut at manifest positions) always keep manifest order.

        COLD-STREAM runs (``--cold-stream`` on a cold remote cohort)
        default to completion order: the whole point of the streaming
        cold path is that fetch → decode → build → put runs as one
        completion-ordered pipeline per shard with no inter-phase
        barrier, so the device accumulator starts before the last shard
        is off the wire. Each per-shard fetch+decode is an
        ``ingest.fetch`` span and the whole stream an ``ingest.stream``
        span (with the ``ingest.stream`` fault seam inside the per-
        shard retry loop — a mid-pipeline stall/error/truncate retries
        per ``--shard-retries`` and G stays bit-identical, pinned by
        the chaos tests).
        """
        from spark_examples_tpu import obs
        from spark_examples_tpu.genomics.mirror import tick_cold_stream_shard
        from spark_examples_tpu.resilience import faults
        from spark_examples_tpu.utils.concurrency import (
            completion_parallel_map,
            ordered_parallel_map,
        )

        vsid = self.conf.variant_set_ids[0]
        shards = self._manifest()
        if self.conf.min_allele_frequency is not None:
            print(
                f"Min allele frequency {self.conf.min_allele_frequency}."
            )
        cold = self._cold_stream_active()
        order = getattr(self.conf, "ingest_order", "auto")
        if order == "auto":
            # An EXPLICIT --ingest-order is always honored; only the
            # default resolves by run shape.
            order = "completion" if cold else "manifest"
            if cold:
                print(
                    "Cold-stream ingest: completion-ordered "
                    "fetch-decode-build-put pipeline (mirror writes "
                    "through in the background).",
                    file=sys.stderr,
                )

        def extract(shard):
            def fetch():
                faults.inject("ingest.stream", key=str(shard))
                with obs.span("ingest.fetch", shard=str(shard)):
                    return self.source.stream_carrying_csr(
                        vsid,
                        shard,
                        self.index.indexes,
                        self.conf.min_allele_frequency,
                    )

            return self._shard_attempt(shard, fetch)

        pmap = (
            completion_parallel_map
            if order == "completion"
            else ordered_parallel_map
        )
        with obs.span(
            "ingest.stream", shards=len(shards), order=order, cold=cold
        ):
            for pair in pmap(extract, shards, self._ingest_workers()):
                if cold:
                    tick_cold_stream_shard("accumulated")
                yield pair

    def _fused_multi_possible(self) -> bool:
        """Keyed fused ingest for multi-dataset join/merge: identity
        payloads + carrying indices straight from records (no
        --debug-datasets, source implements stream_carrying_keyed)."""
        return (
            len(self.conf.variant_set_ids) > 1
            and not self.conf.debug_datasets
            and hasattr(self.source, "stream_carrying_keyed")
        )

    def get_calls_fused_multi(self) -> Iterator[List[int]]:
        """Fused multi-dataset ingest: keyed triples per dataset →
        identity join/merge, same observable behavior as the staged path
        (parity-tested), without Call/Variant materialization."""
        shards = self._manifest()
        if self.conf.min_allele_frequency is not None:
            for _ in self.conf.variant_set_ids:
                # One parity print per dataset (filter_dataset prints per
                # stream in the staged path).
                print(
                    f"Min allele frequency "
                    f"{self.conf.min_allele_frequency}."
                )
        return self._keyed_calls(shards, _contig_runs_unique(shards))

    def _keyed_calls(self, shards, contig_runs_unique: bool):
        """The ONE keyed multi-dataset ingest recipe (worker-budget
        split + keyed streams + identity join/merge), shared by the full
        fused path and the elastic per-unit path so the two can never
        diverge."""
        from spark_examples_tpu.genomics.datasets import calls_stream_keyed

        # One worker pool per dataset stream runs concurrently under
        # calls_stream_keyed — split the budget so K datasets never
        # oversubscribe the host K-fold.
        per_stream = max(
            1,
            self._ingest_workers() // len(self.conf.variant_set_ids),
        )

        def keyed(vsid: str):
            yield from self._parallel_shard_calls(
                vsid,
                shards,
                stream_method=self.source.stream_carrying_keyed,
                workers=per_stream,
            )

        return calls_stream_keyed(
            [keyed(v) for v in self.conf.variant_set_ids],
            contig_runs_unique=contig_runs_unique,
        )

    @staticmethod
    def _debug_wrap(stream):
        for v in stream:
            alt = "".join(v.alternate_bases or ())
            print(
                f"{v.contig}: ({v.start}, {v.end}) "
                f"ref={v.reference_bases or ''} alt={alt}"
            )
            yield v

    # -- stage 4: the Gramian ------------------------------------------------

    def _mesh_spans_processes(self) -> bool:
        from spark_examples_tpu.parallel.mesh import mesh_spans_processes

        if self.mesh is None:
            return False
        return mesh_spans_processes(self.mesh)

    def _sample_sharded(self) -> bool:
        """Shard the N×N Gramian over the mesh instead of replicating it.

        Explicit via --sample-sharded; auto when N exceeds the threshold
        (the 100k-sample stress regime, where a replicated G would be tens
        of GB per device — the wall the reference hit at ~50k samples,
        VariantsPca.scala:176-177).
        """
        if self.conf.sample_sharded is not None:
            return self.conf.sample_sharded
        return self.cohort.size > self.conf.sample_shard_threshold

    # -- cohort sample restriction (the window-boundary filter) -------------

    def _restrict_calls(self, calls_iter):
        """Full-frame per-variant carrier lists → cohort frame (lists
        with no cohort carrier drop, matching calls_stream's no-carrier
        drop; G is unaffected either way — empty columns are inert)."""
        remap = self._sample_remap
        if remap is None:
            yield from calls_iter
            return
        for calls in calls_iter:
            mapped = [int(remap[i]) for i in calls if remap[i] >= 0]
            if mapped:
                yield mapped

    def _restrict_csr(self, pairs):
        """Full-frame per-shard ``(indices, offsets)`` CSR pairs →
        cohort frame, vectorized (drop + renumber carriers; empty rows
        kept so window composition stays arrival-order-only)."""
        remap = self._sample_remap
        if remap is None:
            yield from pairs
            return
        for pair in pairs:
            if pair is None:
                continue
            indices, offsets = pair
            offsets = np.asarray(offsets, dtype=np.int64)
            if offsets.size <= 1:
                continue
            mapped = remap[np.asarray(indices, dtype=np.int64)]
            keep = mapped >= 0
            kept = np.zeros(mapped.size + 1, dtype=np.int64)
            np.cumsum(keep, out=kept[1:])
            yield mapped[keep], kept[offsets]

    def _restrict_window(self, window):
        """One full-frame ``(indices, lens)`` CSR window → cohort frame
        (the per-window twin of :meth:`_restrict_csr`, used where the
        full-frame stream is shared — delta capture)."""
        remap = self._sample_remap
        if remap is None:
            return window
        window_idx, lens = window
        window_idx = np.asarray(window_idx, dtype=np.int64)
        lens = np.asarray(lens, dtype=np.int64)
        mapped = remap[window_idx]
        keep = mapped >= 0
        row_of = np.repeat(np.arange(lens.size, dtype=np.int64), lens)
        new_lens = np.bincount(
            row_of[keep], minlength=lens.size
        ).astype(np.int64)
        return mapped[keep], new_lens

    def _blocks_to_gramian(self, blocks, g_init=None, prepacked=False):
        n = self.cohort.size
        depth = getattr(self.conf, "prefetch_depth", 2)
        if self._mesh_spans_processes():
            # Pod mode: the mesh covers every process; each host feeds its
            # manifest slice as the process-local shard of global blocks
            # and XLA reduces over ICI/DCN — the result is already global.
            from spark_examples_tpu.parallel.sharded import (
                gramian_blockwise_global,
                sharded_gramian_blockwise_global,
            )

            if self._sample_sharded():
                g = sharded_gramian_blockwise_global(
                    blocks, n, self.mesh, packed=True, prefetch_depth=depth
                )
            else:
                g = gramian_blockwise_global(
                    blocks, n, self.mesh, packed=True, prefetch_depth=depth
                )
        elif self.mesh is not None:
            from spark_examples_tpu.parallel.sharded import (
                sharded_gramian_blockwise,
            )

            g = sharded_gramian_blockwise(
                blocks, n, self.mesh, packed=True, prefetch_depth=depth
            )
        else:
            # packed=True: blocks_from_calls yields 0/1 indicators, so the
            # bit-packed transfer (8× fewer host→device bytes; on-chip
            # measured 4.5× on the blockwise phase, PERFORMANCE.md) is
            # bit-identical and strictly faster on any bandwidth-bound
            # link. prepacked: the native ingest engine already produced
            # packbits bytes — the feed skips the host pack entirely.
            g = gramian_blockwise(
                blocks,
                n,
                packed=True,
                prepacked=prepacked,
                prefetch_depth=depth,
            )
        if g_init is not None:
            g = g + jax.numpy.asarray(g_init, dtype=g.dtype)
        return g

    def get_similarity_matrix(self, calls: Iterable[List[int]]):
        """Stream call blocks through the device accumulator → (N, N) G.

        Multi-host: this host's partial Gramian (over its manifest slice)
        is summed across processes — one DCN all-reduce replaces the
        reference's N²-entry shuffle (VariantsPca.scala:190).
        """
        blocks = blocks_from_calls(
            self._restrict_calls(calls),
            self.cohort.size,
            self.conf.block_variants,
        )
        return self._gramian_from_block_stream(blocks)

    def get_similarity_matrix_csr(self, csr_pairs):
        """CSR-direct twin of :meth:`get_similarity_matrix` — identical
        blocks bit-for-bit (pinned by tests), built by vectorized scatter
        instead of per-variant Python lists.

        On the replicated-G (meshless) route the blocks are produced by
        the PARALLEL NATIVE INGEST ENGINE: ``--ingest-workers`` builder
        threads scatter bit-packed panels directly from the sidecar
        ``(indices, offsets)`` windows (native ``csr_to_packed_blocks``
        releases the GIL; no int8 densify intermediate), feeding
        completion-order into the double-buffered device feed — G is
        bit-identical under any block arrival order (integer-exact
        accumulation, pinned by test). Mesh layouts keep the int8 block
        stream (their accumulators pad the sample axis before packing).
        """
        csr_pairs = self._restrict_csr(csr_pairs)
        if self.mesh is None:
            from spark_examples_tpu.arrays.blocks import (
                packed_blocks_from_csr,
            )

            blocks = packed_blocks_from_csr(
                csr_pairs,
                self.cohort.size,
                self.conf.block_variants,
                workers=self._block_builder_workers(),
                attempt=self._build_attempt,
            )
            return self._gramian_from_block_stream(blocks, prepacked=True)
        from spark_examples_tpu.arrays.blocks import blocks_from_csr

        blocks = blocks_from_csr(
            csr_pairs, self.cohort.size, self.conf.block_variants
        )
        return self._gramian_from_block_stream(blocks)

    @staticmethod
    def _cancellable_blocks(blocks):
        """Soft-deadline seam (utils/softcancel.py): the check sits at
        BLOCK boundaries — between one accumulation step and the next —
        so a run-wrapper deadline (scripts/tpu_run.sh) cancels with no
        device dispatch in flight, never a mid-dispatch SIGKILL."""
        from spark_examples_tpu.utils import softcancel

        for block in blocks:
            softcancel.check("gramian block boundary")
            yield block

    def _gramian_from_block_stream(self, blocks, prepacked=False):
        # One armed phase for the whole uncheckpointed accumulation: the
        # timeout must budget full ingest (use checkpointed rounds for
        # finer granularity on long runs).
        with self._watchdog().armed("ingest+gramian collectives"):
            g = self._blocks_to_gramian(
                self._cancellable_blocks(blocks), prepacked=prepacked
            )
            if jax.process_count() > 1 and not self._mesh_spans_processes():
                # Host-local accumulation (no global mesh): merge the
                # per-host partials over DCN. The global-mesh path needs
                # no merge — its result is already the global G.
                from spark_examples_tpu.parallel.distributed import (
                    allreduce_gramian,
                )

                g = allreduce_gramian(g)
        return g

    def _sparse_selected(self) -> bool:
        """Route the Gramian through the sparse-aware engine?

        ``--pca-mode sparse`` forces it; ``auto`` selects it for the
        biobank shape — a sample-sharded mesh (G tiled, no N×N on any
        device) on an uncheckpointed run, whether the mesh is
        host-local (single-process) or process-spanning (the pod
        carrier-allgather protocol). Everything else keeps the dense
        MXU tiers (which beat the scatter at common-variant density —
        the per-window density gate still routes dense-ish windows onto
        the MXU *inside* the sparse engine either way); in particular a
        host-local mesh on a multi-process DP run stays dense (each
        host would tile the FULL G rather than a pod share).
        """
        mode = self.conf.pca_mode
        if mode == "sparse":
            return True
        if mode != "auto":
            return False
        return (
            self.mesh is not None
            and (
                self._mesh_spans_processes()
                or jax.process_count() == 1
            )
            and not self.conf.checkpoint_dir
            and self._sample_sharded()
        )

    def _sparse_device_g_bytes(self) -> int:
        """Per-device bytes the sparse accumulator's G occupies — the
        streaming-sparse footprint bound: the largest f32 accumulator
        tile any one of this host's ADDRESSABLE devices holds
        (``(N/rows)·(N/cols)`` on a mesh; the full N² when meshless).
        The accumulator lives in device memory and the host holds one
        window at a time (the sparse engine never accumulates on the
        host), so the bound belongs to one device: four chips holding a
        quarter of G each are each as full as one chip holding a
        quarter-size G."""
        n = self.cohort.size
        itemsize = 4  # f32 accumulator, exact below 2^24 counts
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from spark_examples_tpu.arrays.blocks import (
                round_up_multiple,
            )
            from spark_examples_tpu.parallel.sharded import (
                _axis_product,
                _mesh_axes,
            )

            d_axis, m_axis = _mesh_axes(self.mesh)
            spec = PartitionSpec(d_axis, m_axis)
            n_padded = round_up_multiple(
                n, _axis_product(self.mesh, spec)
            )
            sharding = NamedSharding(self.mesh, spec)
            tiles = sharding.addressable_devices_indices_map(
                (n_padded, n_padded)
            )
            largest = 0
            for row_sl, col_sl in tiles.values():
                rows = (row_sl.stop or n_padded) - (row_sl.start or 0)
                cols = (col_sl.stop or n_padded) - (col_sl.start or 0)
                largest = max(largest, rows * cols * itemsize)
            return largest
        return n * n * itemsize

    # The auto-sketch trigger: the same per-device budget the streaming-
    # sparse footprint refusal enforces (get_similarity_matrix_stream's
    # max_device_bytes default). Auto stays conservative — every exact
    # path wins below this bound; only where N² would REFUSE does the
    # approximate engine take over.
    SKETCH_AUTO_G_BYTES = 4 << 30

    def sketch_selected(self) -> bool:
        """Public probe for serving callers: will :meth:`ingest_gramian`
        return a Gramian-free :class:`~spark_examples_tpu.ops.sketch.
        SketchPanel` instead of an (N, N) array? (The delta/gang tiers
        must route around such jobs — there is no G to cache, correct,
        or stack.)"""
        return self._sketch_selected()

    def _sketch_selected(self) -> bool:
        """Route ingest through the Gramian-free sketch engine?

        ``--pca-mode sketch`` forces it; ``auto`` selects it ONLY where
        the exact paths are architecturally refused — an uncheckpointed
        run whose per-device Gramian tile footprint
        (:meth:`_sparse_device_g_bytes`, the same bound the streaming
        footprint refusal enforces) exceeds the 4 GiB budget. Below
        that bound every exact tier is both feasible and preferable
        (bit-exact, no tolerance contract), so auto never trades
        exactness for nothing.
        """
        mode = self.conf.pca_mode
        if mode == "sketch":
            return True
        if mode != "auto":
            return False
        return (
            not self.conf.checkpoint_dir
            and self._sparse_device_g_bytes() > self.SKETCH_AUTO_G_BYTES
        )

    def _sketch_panel(self):
        """Sketch-engine ingest: stream cohort-frame CSR carrier
        windows into an (N, k+p) randomized panel — the ``--pca-mode
        sketch`` replacement for every N×N accumulation path
        (ops/sketch.py has the math and the tolerance contract).
        ``windows_factory`` returns a FRESH stream per call because
        each ``--sketch-power-iters`` pass re-streams the cohort."""
        from spark_examples_tpu.utils import softcancel

        def windows_factory():
            for window in self._cohort_windows():
                softcancel.check("sketch panel window boundary")
                yield window

        with self._watchdog().armed("sketch ingest+panel"):
            if self.mesh is not None:
                from spark_examples_tpu.parallel.sharded import (
                    sharded_sketch_panel,
                )

                return sharded_sketch_panel(
                    windows_factory,
                    self.cohort.size,
                    self.conf.num_pc,
                    self.mesh,
                    oversample=self.conf.sketch_oversample,
                    power_iters=self.conf.sketch_power_iters,
                    seed=self.conf.sketch_seed,
                    density_threshold=self.conf.sparse_density_threshold,
                    block_variants=self.conf.block_variants,
                    pipeline_depth=self.conf.pod_pipeline_depth,
                    coalesce_variants=self.conf.pod_coalesce_variants,
                )
            from spark_examples_tpu.ops.sketch import (
                sketch_panel_blockwise,
            )

            return sketch_panel_blockwise(
                windows_factory,
                self.cohort.size,
                self.conf.num_pc,
                oversample=self.conf.sketch_oversample,
                power_iters=self.conf.sketch_power_iters,
                seed=self.conf.sketch_seed,
                density_threshold=self.conf.sparse_density_threshold,
                block_variants=self.conf.block_variants,
            )

    def _windows_to_gramian(self, windows):
        """CSR carrier windows → finished G via the sparse-aware engine
        (the ONE accumulation recipe both ``--pca-mode sparse`` ingest
        and the stream alternate share): tile-sharded scatter on any
        mesh — host-local, or process-spanning through the per-step
        carrier-allgather protocol (each process feeds its manifest
        slice; the result is already the global G, no merge) — and
        single-device accumulation when meshless, with the per-window
        density gate routing dense windows onto the MXU inside either
        engine. Multi-process runs whose G is NOT process-spanning
        (meshless, or a forced-sparse HOST-LOCAL mesh where each host
        tiled only its manifest slice over its own devices) merge
        per-host partials over DCN exactly like the dense tiers.
        Per-shard retry seams
        live in the window PRODUCERS (upstream of any collective, the
        ``_synced_block_stream`` rule), so a retried-then-failed shard
        raises through the synced stream on every process together."""

        def cancellable():
            from spark_examples_tpu.utils import softcancel

            for window in windows:
                softcancel.check("sparse gramian window boundary")
                yield window

        with self._watchdog().armed("sparse ingest+gramian"):
            if self.mesh is not None:
                from spark_examples_tpu.parallel.sharded import (
                    sparse_sharded_gramian_blockwise,
                )

                g = sparse_sharded_gramian_blockwise(
                    cancellable(),
                    self.cohort.size,
                    self.mesh,
                    density_threshold=self.conf.sparse_density_threshold,
                    block_variants=self.conf.block_variants,
                    pipeline_depth=self.conf.pod_pipeline_depth,
                    coalesce_variants=self.conf.pod_coalesce_variants,
                )
                if (
                    not self._mesh_spans_processes()
                    and jax.process_count() > 1
                ):
                    # Forced sparse on a HOST-LOCAL mesh in a
                    # multi-controller run: every step fed only this
                    # host's slice with zero collectives, so g is a
                    # per-host partial (the process-SPANNING mesh is
                    # already the global sum and allreduce_gramian
                    # refuses it).
                    from spark_examples_tpu.parallel.distributed import (
                        allreduce_gramian,
                    )

                    g = allreduce_gramian(g)
                return g
            from spark_examples_tpu.ops.sparse import (
                sparse_gramian_blockwise,
            )

            g = sparse_gramian_blockwise(
                cancellable(),
                self.cohort.size,
                density_threshold=self.conf.sparse_density_threshold,
                block_variants=self.conf.block_variants,
            )
            if jax.process_count() > 1:
                from spark_examples_tpu.parallel.distributed import (
                    allreduce_gramian,
                )

                g = allreduce_gramian(g)
            return g

    def _cohort_windows(self, restrict: bool = True):
        """Route the best available tier's output as CSR carrier
        windows (never densified blocks). The CSR sidecar tier feeds
        windows straight from ``(indices, offsets)`` pairs; call-list
        tiers go through ``windows_from_calls`` — same window
        composition as the dense path's block composition, so
        sparse-vs-dense G bit-identity is comparable window for window.
        ``restrict=False`` yields FULL-frame windows regardless of any
        cohort sample restriction — the delta/gang serving paths build
        per-cohort views from one shared full-frame stream."""
        from spark_examples_tpu.arrays.blocks import (
            csr_windows,
            windows_from_calls,
        )

        if self._fused_csr_possible():
            pairs = self.get_csr_fused()
            if restrict:
                pairs = self._restrict_csr(pairs)
            return csr_windows(pairs, self.conf.block_variants)
        if self._fused_ingest_possible():
            calls = self.get_calls_fused()
        elif self._fused_multi_possible():
            calls = self.get_calls_fused_multi()
        else:
            data = self.get_data()
            filtered = [self.filter_dataset(d) for d in data]
            calls = self.get_calls(filtered)
        if restrict:
            calls = self._restrict_calls(calls)
        return windows_from_calls(calls, self.conf.block_variants)

    def _gramian_sparse(self):
        """Sparse-aware ingest: cohort-frame CSR carrier windows into
        :meth:`_windows_to_gramian`."""
        return self._windows_to_gramian(self._cohort_windows())

    # -- serving entry points: window capture, deltas ------------------------

    def ingest_gramian_windows(self, window_sink=None):
        """Meshless window-route ingest for the delta-aware serving
        engine: same finished G as :meth:`ingest_gramian` (integer-exact
        accumulation — bit-identical across routes, pinned by tests),
        but fed through the sparse engine's window stream so the
        FULL-frame windows can be captured into ``window_sink`` on the
        way (the delta index's per-base-key window cache) while the
        cohort-restricted view accumulates. Checkpointed and mesh runs
        must keep :meth:`ingest_gramian` (no capture there)."""
        if self.conf.checkpoint_dir or self.mesh is not None:
            raise ValueError(
                "ingest_gramian_windows serves meshless uncheckpointed "
                "runs; use ingest_gramian"
            )

        def stream():
            for window in self._cohort_windows(restrict=False):
                if window_sink is not None:
                    window_sink.append(window)
                yield self._restrict_window(window)

        return self._windows_to_gramian(stream())

    def ingest_gramian_delta(
        self, cached_g, cached_samples, windows=None, window_sink=None
    ):
        """Target-cohort G from a cached ancestor G by exact rank-k
        sample correction (:mod:`spark_examples_tpu.ops.delta`) —
        bit-identical to from-scratch, O(k·N) device work instead of a
        full re-accumulation.

        ``cached_samples`` is the ancestor's callset-id frame (row i of
        ``cached_g`` is that callset). ``windows`` is the base key's
        cached full-frame window list; None re-streams the source (and
        captures into ``window_sink`` so the next delta is
        ingest-free). Returns a host f32 array in this driver's cohort
        frame.
        """
        from spark_examples_tpu.ops.delta import delta_gramian

        full = self.index.indexes
        ancestor = np.asarray(
            [full[cid] for cid in cached_samples], dtype=np.int64
        )
        target = np.asarray(
            [full[cid] for cid in self.cohort.callset_of_index()],
            dtype=np.int64,
        )
        if windows is None:

            def stream():
                for window in self._cohort_windows(restrict=False):
                    if window_sink is not None:
                        window_sink.append(window)
                    yield window

            windows = stream()
        return delta_gramian(
            cached_g, ancestor, target, self.index.size, windows
        )

    def get_similarity_matrix_stream(
        self, calls: Iterable[List[int]], max_device_bytes: int = 4 << 30
    ):
        """Sparse pairwise alternative — getSimilarityMatrixStream parity.

        The reference ships an uncalled alternate that trades the dense
        per-task N×N matrix for O(Σk²) shuffled pair contributions
        (``VariantsPca.scala:248-279``). Since the sparse-aware engine
        landed this IS that algorithm, done right: the calls stream
        feeds CSR carrier windows into the same OOB-drop scatter
        accumulation ``--pca-mode sparse`` runs (tile-sharded over the
        driver's mesh when one is configured), so the O(Σk²) work runs
        on device and the host never holds more than one window.

        FOOTPRINT BOUND (the streaming-sparse bound, replacing the round-4
        verdict #7's 16·N² host peak): the only large allocation left is
        the f32 G itself, in device memory — per device, the largest
        tile one device holds (``(N/rows)·(N/cols)`` on a mesh, N²
        meshless), :meth:`_sparse_device_g_bytes`. ``max_device_bytes``
        refuses only when THAT per-device footprint exceeds the budget;
        callers with the memory opt in explicitly, and a larger mesh
        shrinks each device's tile instead of hitting a hard wall at
        N ≈ 32k.
        """
        from spark_examples_tpu.arrays.blocks import windows_from_calls

        n = self.cohort.size
        need = self._sparse_device_g_bytes()
        if need > max_device_bytes:
            layout = (
                "tiled over the mesh"
                if self.mesh is not None
                else "single-device"
            )
            raise ValueError(
                f"get_similarity_matrix_stream streams through the "
                f"sparse device accumulator: N={n} needs "
                f"{need / 2**30:.2f} GiB of per-device f32 Gramian tile "
                f"({layout}) > the {max_device_bytes / 2**30:.2f} GiB "
                "bound. Shard G over more devices (--mesh-shape shrinks "
                "each device's tile) or pass max_device_bytes explicitly "
                "if the devices have the memory"
            )
        return self._windows_to_gramian(
            windows_from_calls(
                self._restrict_calls(calls), self.conf.block_variants
            )
        )

    def get_similarity_matrix_checkpointed(self):
        """Shard-group ingest with incremental (G, cursor) snapshots.

        Resume semantics (SURVEY.md §5 checkpoint/resume, done better than
        the reference's all-or-nothing objectFile): the deterministic
        manifest + idempotent per-shard ingest make skipping completed
        shards exact. Single-dataset only — N-way merge needs global
        identity state that cannot be cut at shard boundaries.
        """
        from spark_examples_tpu.utils.checkpoint import (
            load_snapshot,
            save_snapshot,
        )
        from spark_examples_tpu.genomics.shards import manifest_digest

        if self.conf.elastic_checkpoint:
            # Elastic supports multi-dataset joins via contig-aligned
            # units; the grid-keyed modes below stay single-set.
            return self._checkpointed_elastic()
        assert len(self.conf.variant_set_ids) == 1, (
            "checkpointed ingest supports a single variantset"
        )
        if self._mesh_spans_processes():
            return self._checkpointed_pod()
        vsid = self.conf.variant_set_ids[0]
        shards = self._manifest()
        checkpoint_dir = self.conf.checkpoint_dir
        # Multi-host: each process checkpoints ITS manifest slice into its
        # own subdirectory (no cross-host file races); partials merge
        # after all hosts complete, exactly as in the uncheckpointed path.
        # The slice depends on the process grid, so the digest pins it.
        host_tag = ""
        if jax.process_count() > 1:
            host_tag = (
                f"|host={jax.process_index()}/{jax.process_count()}"
            )
            checkpoint_dir = os.path.join(
                checkpoint_dir, f"host-{jax.process_index()}"
            )
        # The snapshot key covers everything that determines this host's
        # partial G: the manifest slice, dataset, AF filter, process grid.
        digest = (
            f"{manifest_digest(shards)}|{vsid}"
            f"|af={self.conf.min_allele_frequency}{host_tag}"
        )
        n = self.index.size
        ck = load_snapshot(checkpoint_dir, digest, n)
        done = ck.shards_done if ck else 0
        if ck:
            print(f"Resuming from snapshot: {done}/{len(shards)} shards done.")
        g = ck.g if ck else None

        every = max(1, self.conf.checkpoint_every)
        while done < len(shards):
            # Between groups a snapshot is already on disk — the ideal
            # soft-cancel point: exit here loses zero completed work.
            from spark_examples_tpu.utils import softcancel

            softcancel.check("checkpoint group boundary")
            group = shards[done : done + every]
            g = self._ingest_shard_group(vsid, group, g)
            done += len(group)
            save_snapshot(checkpoint_dir, g, done, digest)
        if g is None:
            g = self._blocks_to_gramian(iter(()))
        if jax.process_count() > 1:
            from spark_examples_tpu.parallel.distributed import (
                allreduce_gramian,
            )

            g = allreduce_gramian(jax.numpy.asarray(g))
        return g

    def _elastic_shared_dir_probe(self, directory, p, world):
        """Verify every host sees ONE checkpoint dir, before any work.

        Write-probe rather than lane fingerprints: on a first run every
        host sees zero lanes, so fingerprints cannot distinguish a shared
        dir from per-host local disks — and discovering that only after a
        crash strands each host's lanes. Every process drops a token,
        barriers, then must see every peer's token. Miss counts are
        exchanged BEFORE tokens are deleted (allgather syncs entry, not
        exit — deleting first lets a fast host remove its token before a
        slow host checks), and EVERY host fails when ANY host missed: a
        one-sided raise would strand the passing hosts in the next
        collective.
        """
        from jax.experimental import multihost_utils

        os.makedirs(directory, exist_ok=True)
        token = os.path.join(directory, f".probe-{p}")
        with open(token, "w") as f:
            f.write(str(p))
        with self._watchdog().armed("elastic shared-dir probe"):
            multihost_utils.process_allgather(np.array([p], np.int64))
        missing = [
            i
            for i in range(world)
            if not os.path.exists(os.path.join(directory, f".probe-{i}"))
        ]
        with self._watchdog().armed("elastic shared-dir probe (exit)"):
            misses = np.asarray(
                multihost_utils.process_allgather(
                    np.array([len(missing)], np.int64)
                )
            ).ravel()
        try:
            os.remove(token)
        except OSError:
            pass
        if int(misses.max()) > 0:
            detail = (
                f"this host cannot see the probe file(s) of "
                f"process(es) {missing}; "
                if missing
                else ""
            )
            raise RuntimeError(
                "elastic multi-host checkpointing requires "
                "--checkpoint-dir on a filesystem every host shares; "
                f"{detail}probe miss counts per process: "
                f"{misses.tolist()}"
            )

    def _checkpointed_elastic(self):
        """Elastic ingest: Spark-task-style work units, any-world-size resume.

        The reference delegates straggler/executor-loss recovery to Spark
        task re-execution (SURVEY.md §2.10 elasticity row;
        ``VariantsRDD.scala:163-165`` merely counts failures). This is the
        TPU-native analog (utils/elastic.py): the GLOBAL manifest is cut
        into fixed units of ``checkpoint_every`` shards; each process
        accumulates its units into a self-describing lane snapshot; resume
        at ANY process count claims surviving lanes and re-slices the
        uncovered units over the live hosts — so a dead host's remaining
        share is re-executed by survivors instead of freezing the job.

        Host-local (DP) accumulation regime only: pod-mode collectives
        need every process in lockstep on one mesh, which is exactly the
        coupling elasticity removes — use the synced-round pod
        checkpointing there. Multi-host elastic runs require the
        checkpoint dir on a shared filesystem (verified by fingerprint
        allgather before any work).
        """
        from jax.experimental import multihost_utils

        from spark_examples_tpu.genomics.shards import manifest_digest
        from spark_examples_tpu.utils import elastic

        if self._mesh_spans_processes():
            raise ValueError(
                "--elastic-checkpoint applies to the host-local (DP) "
                "accumulation regime; a process-spanning mesh needs the "
                "fixed-grid pod checkpointing (omit --elastic-checkpoint)"
            )
        vsids = self.conf.variant_set_ids
        multi = len(vsids) > 1
        shards_all = self._global_manifest()
        every = max(1, self.conf.checkpoint_every)
        if multi:
            # Multi-dataset joins checkpoint EXACTLY when work units
            # never split a contig: the identity join/merge keeps
            # per-contig state (identities hash contig+position+alleles),
            # so whole-contig units reproduce the uninterrupted join
            # row-for-row. The reference's only join resume was the
            # all-or-nothing objectFile (VariantsCommon.scala:52-55).
            if not self._fused_multi_possible():
                raise ValueError(
                    "elastic multi-dataset checkpointing needs the fused "
                    "keyed ingest (a source with stream_carrying_keyed, "
                    "no --debug-datasets)"
                )
            if not _contig_runs_unique(shards_all):
                raise ValueError(
                    "elastic multi-dataset checkpointing requires each "
                    "contig to appear as one contiguous manifest run "
                    "(join state is per-contig; units cut at contig "
                    "boundaries)"
                )
        # Single-set keeps the bare id (digest back-compat with existing
        # lanes); multi-set uses length-prefixed encoding so distinct id
        # lists can never collide (['a','b+c'] vs ['a+b','c']).
        vs_key = (
            vsids[0]
            if not multi
            else ",".join(f"{len(v)}:{v}" for v in vsids)
        )
        digest = (
            f"{manifest_digest(shards_all)}|{vs_key}"
            f"|af={self.conf.min_allele_frequency}|every={every}|elastic"
            + ("|contig-units" if multi else "")
        )
        n = self.index.size
        directory = os.path.join(self.conf.checkpoint_dir, "elastic")
        p, world = jax.process_index(), jax.process_count()
        if world > 1:
            self._elastic_shared_dir_probe(directory, p, world)
        lanes = elastic.load_lanes(directory, digest, n)
        if world > 1:
            fp = bytes.fromhex(elastic.lane_view_fingerprint(lanes))
            with self._watchdog().armed("elastic lane-view agreement"):
                views = np.asarray(
                    multihost_utils.process_allgather(
                        np.frombuffer(fp, dtype=np.uint8)
                    )
                ).reshape(world, -1)
            if not (views == views[0]).all():
                raise RuntimeError(
                    "elastic checkpoint lanes differ across hosts — "
                    "--checkpoint-dir must be on a filesystem every host "
                    "shares for elastic multi-host resume"
                )
        if p == 0:
            # One host prunes digest-orphaned and superseded lane files
            # (safe: every host finished reading lanes at the agreement
            # barrier above; single-process runs have no reader to race).
            elastic.prune_stale_lanes(directory, digest, lanes)
        if multi:
            units = elastic.unit_ranges_contig_aligned(shards_all, every)
        else:
            units = elastic.unit_ranges(len(shards_all), every)
        done = set()
        for lane in lanes:
            done |= lane.units
        remaining = [u for u in range(len(units)) if u not in done]
        # Deterministic claim: sorted lanes and uncovered units round-robin
        # over the CURRENT world size — the same rule at any world size, so
        # a relaunch with fewer (or more) hosts just re-deals the work.
        my_lanes = lanes[p::world]
        my_units = remaining[p::world]
        if done:
            print(
                f"Elastic resume: {len(done)}/{len(units)} units already "
                f"covered by {len(lanes)} lane(s); this process claims "
                f"{len(my_lanes)} lane(s) + {len(my_units)} new unit(s)."
            )
        g = None
        covered = set()
        own_paths = []
        for lane in my_lanes:
            # Payloads load lazily: only CLAIMED lanes' Gramians ever
            # reach this host's memory (listing loaded metadata alone).
            # A payload that fails to decompress (metadata read fine but
            # the g member is corrupt) must not kill resume: this process
            # claimed the lane, so it re-executes the lane's units and
            # the corrupt file is superseded at the next merge.
            try:
                lane_g = lane.load_g()
            except Exception as e:  # noqa: BLE001 — any corruption shape
                print(
                    f"WARNING: claimed lane {lane.path} payload is "
                    f"unreadable ({type(e).__name__}: {e}); re-executing "
                    f"its {len(lane.units)} unit(s).",
                    file=sys.stderr,
                )
                my_units = my_units + sorted(lane.units)
                own_paths.append(lane.path)
                continue
            covered |= lane.units
            own_paths.append(lane.path)
            if g is None:
                # Fresh private array from np.load: in-place accumulation
                # is safe and keeps the peak at two (N, N) arrays, not
                # three — at stress scale each is tens of GB.
                g = lane_g
            else:
                g += lane_g
        if multi and my_units and self.conf.min_allele_frequency is not None:
            for _ in vsids:  # one parity print per dataset stream
                print(
                    f"Min allele frequency "
                    f"{self.conf.min_allele_frequency}."
                )
        from spark_examples_tpu.utils import softcancel

        for u in my_units:
            # Between units the lane snapshot covers everything done —
            # soft-cancel here loses zero completed work.
            softcancel.check("elastic unit boundary")
            lo, hi = units[u]
            if multi:
                g = np.asarray(
                    self._ingest_unit_multi(shards_all[lo:hi], g)
                )
            else:
                g = np.asarray(
                    self._ingest_shard_group(
                        vsids[0], shards_all[lo:hi], g
                    )
                )
            covered.add(u)
            own_paths = [
                elastic.merge_and_supersede(
                    directory, g, covered, digest, own_paths
                )
            ]
        if g is None:
            g = self._blocks_to_gramian(iter(()))
        else:
            g = jax.numpy.asarray(g)
        if world > 1:
            from spark_examples_tpu.parallel.distributed import (
                allreduce_gramian,
            )

            # Unlike pod mode's per-round arming, elastic hosts ingest
            # WITHOUT any sync until this single merge — the first host
            # done waits here for the slowest, so --collective-timeout
            # must budget the whole-run ingest skew (uneven unit deals
            # are routine), not collective latency. The phase name says
            # so, so a fired watchdog diagnostic explains itself.
            with self._watchdog().armed(
                "elastic final allreduce (deadline must cover ingest "
                "skew across hosts — slowest minus fastest host)"
            ):
                g = allreduce_gramian(g)
        return g

    def _checkpointed_pod(self):
        """Pod-mode checkpointing: a globally-synced round cursor.

        Pod block steps are collective, so a per-host cursor cannot resume
        hosts independently — instead every process runs the same number
        of ROUNDS (checkpoint_every shards of its own manifest slice per
        round, zero-filling when its slice runs short), and after each
        collective round the replicated G is snapshotted by every host
        into its own directory with the same global round cursor. Resume
        requires all hosts to agree on the round (verified by allgather);
        disagreement — a crash landing between two hosts' saves — discards
        the snapshots with a warning rather than resuming inconsistently.

        The sample-sharded pod regime checkpoints WITHOUT gathering:
        every host snapshots only its addressable tiles of the
        cross-process-sharded G (``save_sharded_snapshot``), and resume
        re-places each tile through the sharding's own index map — so
        the multi-hour >50k-sample stress runs the reference couldn't
        reach at all (VariantsPca.scala:176-177) get the same
        round-granular resume as the replicated layout, at a per-host
        snapshot cost of one tile set, never one whole G.
        """
        from jax.experimental import multihost_utils

        from spark_examples_tpu.genomics.shards import manifest_digest
        from spark_examples_tpu.utils.checkpoint import (
            load_snapshot,
            save_snapshot,
        )
        # A lost peer stalls survivors in the next collective forever;
        # with --collective-timeout each phase is armed fail-stop (exit
        # 77) so a relaunch can resume all hosts from snapshots instead
        # of hanging the pod (utils/watchdog.py).
        wd = self._watchdog()
        sharded_g = self._sample_sharded()
        vsid = self.conf.variant_set_ids[0]
        mine = self._manifest()
        every = max(1, self.conf.checkpoint_every)
        with wd.armed("manifest-length allgather"):
            lens = np.asarray(
                multihost_utils.process_allgather(
                    np.array([len(mine)], np.int64)
                )
            ).ravel()
        total_rounds = int(-(-int(lens.max()) // every))  # ceil
        checkpoint_dir = os.path.join(
            self.conf.checkpoint_dir, f"host-{jax.process_index()}"
        )
        # The digest pins THIS HOST's manifest slice plus its pod-grid
        # coordinates, round width, and (for sharded G) the mesh layout
        # tiles are keyed to; cross-host schedule consistency is NOT the
        # digest's job — the rounds-allgather below enforces it.
        mesh_tag = ""
        if sharded_g:
            mesh_tag = "|mesh=" + ",".join(
                f"{name}:{size}" for name, size in self.mesh.shape.items()
            )
        digest = (
            f"{manifest_digest(mine)}|{vsid}"
            f"|af={self.conf.min_allele_frequency}"
            f"|pod={jax.process_index()}/{jax.process_count()}|every={every}"
            f"{mesh_tag}"
        )
        n = self.index.size
        if sharded_g:
            local_round, g = self._load_sharded_pod_snapshot(
                checkpoint_dir, digest, n
            )
        else:
            ck = load_snapshot(checkpoint_dir, digest, n)
            local_round = ck.shards_done if ck else 0  # counts ROUNDS
            g = ck.g if ck else None
        with wd.armed("resume-round allgather"):
            rounds = np.asarray(
                multihost_utils.process_allgather(
                    np.array([local_round], np.int64)
                )
            ).ravel()
        start = int(rounds.min())
        if int(rounds.max()) != start:
            print(
                "WARNING: pod snapshot rounds disagree across hosts "
                f"({sorted(int(r) for r in rounds)}); discarding and "
                "re-ingesting from round 0.",
                file=sys.stderr,
            )
            start, g = 0, None
        if start:
            print(
                f"Resuming pod ingest from round {start}/{total_rounds}."
            )
        for r in range(start, total_rounds):
            # Collective round: a host whose slice ran short contributes
            # zero-filled steps via the synced stream inside the pod
            # accumulator, so every process executes the same collectives.
            # The watchdog budget covers the WHOLE round (host ingest +
            # collective accumulate + snapshot) — size the timeout off
            # round wall-clock, not network latency.
            with wd.armed(f"pod round {r + 1}/{total_rounds}"):
                g = self._ingest_shard_group(
                    vsid, mine[r * every : (r + 1) * every], g
                )
                if sharded_g:
                    self._save_sharded_pod_snapshot(
                        checkpoint_dir, g, r + 1, digest
                    )
                else:
                    save_snapshot(
                        checkpoint_dir, np.asarray(g), r + 1, digest
                    )
        if g is None:
            g = self._blocks_to_gramian(iter(()))
        return g

    def _g_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec

        from spark_examples_tpu.parallel.sharded import _mesh_axes

        d_axis, m_axis = _mesh_axes(self.mesh)
        return NamedSharding(self.mesh, PartitionSpec(d_axis, m_axis))

    def _save_sharded_pod_snapshot(self, directory, g, round_, digest):
        """Snapshot this host's tiles of the sharded G (no gather).

        The accumulator's trim step leaves layout choice to GSPMD, so G
        is first pinned to the canonical P(data, model) sharding — a
        collective jit all hosts execute at the same round — making the
        tile geometry deterministic for resume.
        """
        from spark_examples_tpu.utils.checkpoint import (
            save_sharded_snapshot,
        )

        if self._pin_g_jit is None:
            # Built once per driver: a fresh lambda per round would miss
            # the jit cache and re-compile the resharding program every
            # checkpoint round.
            self._pin_g_jit = jax.jit(
                lambda a: a, out_shardings=self._g_sharding()
            )
        g = self._pin_g_jit(g)
        save_sharded_snapshot(directory, g, round_, digest)

    def _load_sharded_pod_snapshot(self, directory, digest, n):
        """→ (rounds_done, sharded G | None) from this host's tile set.

        The stored tiles must cover exactly the CURRENT sharding's
        addressable indices; any mismatch (different mesh/process
        placement than the digest caught) discards the snapshot. The
        rounds value feeds the cross-host agreement check either way.
        """
        from spark_examples_tpu.utils.checkpoint import (
            index_key,
            load_sharded_snapshot,
        )

        loaded = load_sharded_snapshot(directory, digest, n)
        if loaded is None:
            return 0, None
        rounds_done, tiles = loaded
        sharding = self._g_sharding()
        expected = {
            index_key(idx, (n, n))
            for dev, idx in sharding.addressable_devices_indices_map(
                (n, n)
            ).items()
        }
        if expected != set(tiles):
            print(
                "WARNING: sharded snapshot tile set does not match this "
                "mesh placement; discarding.",
                file=sys.stderr,
            )
            return 0, None
        g = jax.make_array_from_callback(
            (n, n), sharding, lambda idx: tiles[index_key(idx, (n, n))]
        )
        return rounds_done, g

    def _ingest_unit_multi(self, group, g):
        """One contig-aligned unit through the fused keyed join → blocks
        accumulated onto g (elastic multi-dataset checkpointing). The
        group holds whole contigs, so the per-contig join state is
        complete within the unit and the joined rows are identical to
        the same contigs' slice of an uninterrupted run."""
        blocks = blocks_from_calls(
            self._keyed_calls(group, contig_runs_unique=True),
            self.index.size,
            self.conf.block_variants,
        )
        return self._blocks_to_gramian(blocks, g_init=g)

    def _ingest_shard_group(self, vsid: str, group, g):
        """Stream one shard group through filter → calls → Gramian blocks,
        accumulating onto g (shared by both checkpointed ingest modes).
        Prefers the CSR-direct tier (bit-identical blocks — parity
        pinned — so snapshots and resume digests are unaffected)."""
        if self._fused_csr_possible():
            pairs = (
                self._shard_attempt(
                    shard,
                    lambda shard=shard: self.source.stream_carrying_csr(
                        vsid,
                        shard,
                        self.index.indexes,
                        self.conf.min_allele_frequency,
                    ),
                )
                for shard in group
            )
            if self.mesh is None:
                # Same parallel native packed production as the
                # uncheckpointed route: snapshots cut at GROUP
                # boundaries, and within a group G is bit-identical
                # under any block completion order, so resume digests
                # are unaffected.
                from spark_examples_tpu.arrays.blocks import (
                    packed_blocks_from_csr,
                )

                blocks = packed_blocks_from_csr(
                    pairs,
                    self.index.size,
                    self.conf.block_variants,
                    workers=self._block_builder_workers(),
                    attempt=self._build_attempt,
                )
                return self._blocks_to_gramian(
                    blocks, g_init=g, prepacked=True
                )
            from spark_examples_tpu.arrays.blocks import blocks_from_csr

            blocks = blocks_from_csr(
                pairs, self.index.size, self.conf.block_variants
            )
            return self._blocks_to_gramian(blocks, g_init=g)
        fused = self._fused_ingest_possible()

        def group_calls():
            if fused:
                yield from self._parallel_shard_calls(vsid, group)
                return
            for shard in group:
                # Materialize per shard so the retry layer can re-execute
                # a failed shard without re-running its predecessors —
                # one shard's call lists, bounded memory.
                yield from self._shard_attempt(
                    shard,
                    lambda shard=shard: list(
                        calls_stream(
                            [
                                self.filter_dataset(
                                    self.source.stream_variants(
                                        vsid, shard
                                    )
                                )
                            ],
                            self.index.indexes,
                        )
                    ),
                )

        blocks = blocks_from_calls(
            group_calls(), self.index.size, self.conf.block_variants
        )
        return self._blocks_to_gramian(blocks, g_init=g)

    # -- stage 5: eigendecomposition ----------------------------------------

    def compute_pca(self, g, timer=None) -> List[Tuple[str, float, float]]:
        with self._watchdog().armed("pca collectives"):
            return self._compute_pca(g, timer)

    def _pca_fused_eligible(self, g) -> bool:
        """Route the PCA stage through the fused single-dispatch finish?

        The fused finish (ops/fused.py) composes with ANY ingest tier —
        it only consumes the finished G — so eligibility is about the
        execution regime, not the ingest mode: single process, no mesh
        (G replicated on one device), not --precise (host f64 is its own
        route). ``auto`` additionally gates on N ≤ --dense-eigh-limit,
        the same scale knob the sharded path uses for its dense/iterative
        split; ``fused`` forces it at any N (config validity was checked
        in __init__, before ingest).
        """
        mode = self.conf.pca_mode
        if mode == "stream":
            return False
        if (
            self.conf.precise
            or self.mesh is not None
            or jax.process_count() > 1
            or not getattr(g, "is_fully_addressable", True)
        ):
            return False
        if mode == "fused":
            return True
        return self.cohort.size <= self.conf.dense_eigh_limit

    def _compute_pca(self, g, timer=None) -> List[Tuple[str, float, float]]:
        import jax.numpy as jnp

        from spark_examples_tpu.ops.sketch import SketchPanel

        if isinstance(g, SketchPanel):
            # Gramian-free finish: row sums rode the panel's companion
            # column (integer-exact in f32), so the parity print
            # survives without G; the eigensolve is the Nyström/TSQR
            # finish with the same gap check and sign convention as
            # every exact tier.
            from spark_examples_tpu.ops.sketch import sketch_eig

            nonzero = int((np.asarray(g.row_sums) > 0).sum())
            print(
                f"Non zero rows in matrix: {nonzero} / "
                f"{self.cohort.size}."
            )  # VariantsPca.scala:207-208
            coords, _ = sketch_eig(g, self.conf.num_pc, timer=timer)
            return self._emit_tuples(coords)

        if self._pca_fused_eligible(g):
            from spark_examples_tpu.ops.fused import (
                RankDeficientGramianError,
                fused_finish,
            )

            # One device program (centering → CholeskyQR subspace eig →
            # row sums), one packed readback. Row sums ride the same
            # readback for the parity print below
            # (VariantsPca.scala:207-208). --eig-tol threads through as
            # the convergence target (the fused path checks its own Ritz
            # residuals and retries with doubled iterations before
            # warning — fused_finish docstring).
            kwargs = (
                {"resid_warn": self.conf.eig_tol}
                if self.conf.eig_tol is not None
                else {}
            )
            try:
                coords, _, row_sums = fused_finish(
                    jnp.asarray(g), self.conf.num_pc, timer=timer, **kwargs
                )
            except RankDeficientGramianError as e:
                # The fused finish measured a rank-deficient centered
                # Gramian (e.g. duplicated samples from a multi-dataset
                # merge) — CholeskyQR cannot factor its panel. Under
                # AUTO selection that must not kill the run: dense eigh
                # handles rank deficiency exactly, and N here is ≤
                # --dense-eigh-limit by the eligibility gate, so fall
                # back loudly. A forced --pca-mode fused keeps the hard
                # error (the user asked for exactly that program).
                if self.conf.pca_mode == "fused":
                    raise
                import warnings

                warnings.warn(
                    f"{e}; falling back to the dense-eigh finish (exact "
                    "on rank-deficient spectra)"
                )
                if timer is not None:
                    timer.note(
                        "fused finish rank-deficient -> dense-eigh fallback"
                    )
            else:
                nonzero = int((np.asarray(row_sums) > 0).sum())
                print(
                    f"Non zero rows in matrix: {nonzero} / "
                    f"{self.cohort.size}."
                )
                return self._emit_tuples(coords)

        addressable = getattr(g, "is_fully_addressable", True)
        # Row sums reduce on device (mesh collectives when sharded); only
        # the N-vector reaches the host for the parity print. A
        # process-spanning G needs the reduction replicated so every host
        # can read the vector.
        if addressable:
            row_sums = np.asarray(jnp.sum(jnp.asarray(g), axis=1))
        else:
            from jax.sharding import NamedSharding, PartitionSpec

            row_sums = np.asarray(
                jax.jit(
                    lambda a: jnp.sum(a, axis=1),
                    out_shardings=NamedSharding(
                        self.mesh, PartitionSpec(None)
                    ),
                )(g)
            )
        nonzero = int((row_sums > 0).sum())
        print(
            f"Non zero rows in matrix: {nonzero} / {self.cohort.size}."
        )  # VariantsPca.scala:207-208
        if self.conf.precise:
            # Host-f64 LAPACK path: implies N is gatherable (the reference
            # gathered the whole matrix to its driver JVM at any N).
            if not addressable:
                from jax.sharding import NamedSharding, PartitionSpec

                g = jax.jit(
                    lambda a: a,
                    out_shardings=NamedSharding(
                        self.mesh, PartitionSpec(None, None)
                    ),
                )(g)
            from spark_examples_tpu.ops.pcoa import topk_with_gap_check

            gh = np.asarray(g)
            coords, _ = topk_with_gap_check(
                lambda kk: mllib_principal_components_reference(gh, kk),
                self.conf.num_pc,
                self.cohort.size,
                timer=timer,
                vals_are_squared=True,  # covariance eigenvalues = λ(C)²/(n−1)
            )
        elif self.mesh is not None:
            from spark_examples_tpu.parallel.sharded import sharded_pcoa

            coords, _ = sharded_pcoa(
                g,
                self.conf.num_pc,
                self.mesh,
                dense_eigh_limit=self.conf.dense_eigh_limit,
                timer=timer,
                eig_tol=self.conf.eig_tol,
            )
        else:
            from spark_examples_tpu.ops.pcoa import topk_with_gap_check

            # k+1 eigenpairs so the default single-host dense path gets
            # the same flat-spectrum detection as the sharded paths.
            coords, _ = topk_with_gap_check(
                lambda kk: pcoa(g, kk),
                self.conf.num_pc,
                self.cohort.size,
                timer=timer,
            )
        return self._emit_tuples(coords)

    def _emit_tuples(self, coords) -> List[Tuple[str, float, float]]:
        coords = np.asarray(coords)
        callset_ids = self.cohort.callset_of_index()
        # The reference emits exactly two components regardless of --num-pc
        # (VariantsPca.scala:228-230: array(i), array(i + numRows)).
        pc2 = coords[:, 1] if coords.shape[1] > 1 else np.zeros(len(coords))
        return [
            (callset_ids[i], float(coords[i, 0]), float(pc2[i]))
            for i in range(self.cohort.size)
        ]

    # -- stage 6: emission ---------------------------------------------------

    def collect_result(
        self, result: Sequence[Tuple[str, float, float]]
    ) -> List[Tuple[str, float, float, str]]:
        """``emitResult``'s row shape — ``(name, pc1, pc2, dataset)``
        sorted by name — WITHOUT the emission side effects: the return
        surface the serving tier (serving/engine.py) hands back to
        clients, and the one place the name/dataset join lives."""
        return sorted(
            (
                self.cohort.names[cid],
                pc1,
                pc2,
                cid.split("-")[0],  # dataset label, VariantsPca.scala:235
            )
            for cid, pc1, pc2 in result
        )

    def emit_result(self, result: Sequence[Tuple[str, float, float]]) -> None:
        from spark_examples_tpu.parallel.distributed import is_coordinator

        if not is_coordinator():
            return  # coordinator-only emission (the driver role)
        with_names = self.collect_result(result)
        for name, pc1, pc2, dataset in with_names:
            print(f"{name}\t{dataset}\t{pc1}\t{pc2}")
        if self.conf.output_path:
            path = self.conf.output_path + "-pca.tsv"
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                for name, pc1, pc2, dataset in with_names:
                    f.write(f"{name}\t{pc1}\t{pc2}\t{dataset}\n")

    # -- observability -------------------------------------------------------

    def report_io_stats(self) -> None:
        if self._speculated_shards:
            # Host-local observability line (Spark logs speculation per
            # executor; this is the per-host analog).
            print(
                f"Speculative shard attempts on this host: "
                f"{self._speculated_shards}.",
                file=sys.stderr,
            )
        stats = getattr(self.source, "stats", None)
        if stats is None:
            return
        if jax.process_count() > 1:
            from spark_examples_tpu.parallel.distributed import (
                allreduce_host_stats,
                is_coordinator,
            )

            stats = allreduce_host_stats(stats)
            if not is_coordinator():
                return
        # The job-end driver-merged totals (cross-host after the
        # all-reduce above) — the authoritative Spark-accumulator-merge
        # analog — recorded as registry gauges so the run manifest
        # carries them distinctly from the per-instance collector sums.
        from spark_examples_tpu import obs
        from spark_examples_tpu.utils.stats import COUNTER_FIELDS

        reg = obs.get_registry()
        for name, value in zip(COUNTER_FIELDS, stats.as_vector()):
            reg.gauge(
                f"genomics_io_merged_{name}",
                "Driver-merged job-end IoStats totals "
                "(allreduce_host_stats across hosts)",
            ).set(float(value))
        print(stats.report())

    def stop(self) -> None:
        """No cluster to tear down (sc.stop parity no-op)."""

    # -- orchestration -------------------------------------------------------

    def ingest_gramian(self):
        """Stages 1-4 as one call: route the configured ingest tier and
        return the finished (N, N) Gramian.

        This is the run loop's ingest half, extracted so the serving
        engine (serving/engine.py) can drive the same tier routing per
        job without the emission/report side effects of :meth:`run` —
        the two callers provably cannot diverge because this is the only
        copy of the routing.
        """
        if self.conf.checkpoint_dir and (
            len(self.conf.variant_set_ids) == 1
            or self.conf.elastic_checkpoint
        ):
            return self.get_similarity_matrix_checkpointed()
        if self._sketch_selected():
            # Gramian-free: the return value is a SketchPanel, not an
            # (N, N) array — compute_pca dispatches on it, and serving
            # callers that cache/delta G must route around it
            # (engine.run's sketch branch).
            return self._sketch_panel()
        if self._sparse_selected():
            return self._gramian_sparse()
        if self._fused_csr_possible():
            return self.get_similarity_matrix_csr(self.get_csr_fused())
        if self._fused_ingest_possible():
            return self.get_similarity_matrix(self.get_calls_fused())
        if self._fused_multi_possible():
            return self.get_similarity_matrix(self.get_calls_fused_multi())
        data = self.get_data()
        filtered = [self.filter_dataset(d) for d in data]
        calls = self.get_calls(filtered)
        return self.get_similarity_matrix(calls)

    def run(self) -> List[Tuple[str, float, float]]:
        """main() stage order — VariantsPca.scala:38-50."""
        from spark_examples_tpu.utils.tracing import StageTimer, profiler_trace

        timer = StageTimer()
        with profiler_trace(self.conf.trace_dir):
            with timer.stage("ingest+gramian"):
                g = self.ingest_gramian()
            with timer.stage("pca"):
                result = self.compute_pca(g, timer=timer)
            with timer.stage("emit"):
                self.emit_result(result)
        self.report_io_stats()
        print(timer.report())
        self.stop()
        return result
