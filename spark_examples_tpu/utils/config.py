"""Flag system: CLI parity with GenomicsConf/PcaConf plus mesh/TPU flags.

Two-level declarative config mirroring the scallop hierarchy
(``GenomicsConf.scala:31-101``): :class:`GenomicsConfig` carries the common
flags with the reference defaults (1M bases/shard, BRCA1 region, Platinum
Genomes set id); :class:`PcaConfig` adds the PCA-driver extras. Spark-only
knobs (``--num-reduce-partitions``, ``--spark-master``) are accepted for CLI
compatibility but map onto mesh/topology flags.
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass, field
from typing import List, Optional

from spark_examples_tpu.arrays.blocks import DEFAULT_BLOCK_VARIANTS
from spark_examples_tpu.ops.pcoa import (
    DEFAULT_RANDOMIZED_OVERSAMPLE,
    DEFAULT_SKETCH_POWER_ITERS,
)
from spark_examples_tpu.resilience.breaker import (
    DEFAULT_COOLDOWN_S,
    DEFAULT_FAILURE_THRESHOLD,
)
from spark_examples_tpu.resilience.policy import RetryPolicy as _RetryPolicy
from spark_examples_tpu.genomics.shards import (
    BRCA1_REFERENCES,
    DEFAULT_BASES_PER_SHARD,
    SexChromosomeFilter,
    Shard,
    shards_for_all_references,
    shards_for_references,
)

__all__ = [
    "GenomicsConfig",
    "PCA_MODES",
    "PcaConfig",
    "add_analyze_flags",
    "add_genomics_flags",
    "add_pca_flags",
]

# THE --pca-mode registry: the one place the allowed-mode set lives.
# argparse choices, the driver's programmatic validation + its error
# message, the serving JobSpec's per-job override validation, and the
# auto-selection gates all derive from this tuple — adding an engine is
# a one-line change here (a sync test pins every consumer against it).
PCA_MODES = ("auto", "fused", "stream", "sparse", "sketch")

def _csv_list(value: str) -> List[str]:
    """argparse type for comma-separated id lists (empty items dropped,
    so a trailing comma is not a silent empty id)."""
    return [item for item in value.split(",") if item]


# Reference well-known variantset ids (SearchVariantsExample.scala:27-31).
PLATINUM_GENOMES = "3049512673186936334"
THOUSAND_GENOMES_PHASE1 = "10473108253681171589"
THOUSAND_GENOMES_PHASE3 = "4252737135923902652"


@dataclass
class GenomicsConfig:
    bases_per_partition: int = DEFAULT_BASES_PER_SHARD
    client_secrets: Optional[str] = None
    input_path: Optional[str] = None
    num_reduce_partitions: int = 10  # accepted for parity; unused by XLA
    output_path: Optional[str] = None
    references: str = BRCA1_REFERENCES
    variant_set_ids: List[str] = field(
        default_factory=lambda: [PLATINUM_GENOMES]
    )
    # TPU-native additions (replace --spark-master):
    mesh_shape: Optional[str] = None  # e.g. "data:4,model:2"
    block_variants: int = DEFAULT_BLOCK_VARIANTS
    # Resilience layer (spark_examples_tpu.resilience): declarative
    # retry policy for the network tiers (HTTP + gRPC), per-endpoint
    # circuit breaking, and the deterministic fault-injection plane.
    # Defaults derive from the layer itself (RetryPolicy / breaker
    # constants) so dataclass, flags, and direct construction agree.
    rpc_retries: int = _RetryPolicy.max_attempts  # attempts (1 = no retry)
    rpc_retry_deadline: Optional[float] = None  # wall-clock budget (s)
    breaker_threshold: int = DEFAULT_FAILURE_THRESHOLD
    breaker_cooldown: float = DEFAULT_COOLDOWN_S
    grpc_idle_timeout: Optional[float] = 120.0  # per-read stream idle (s)
    fault_plan: Optional[str] = None  # FaultPlan JSON (inline or a path)

    def shards(
        self,
        all_references: bool = False,
        sex_filter: SexChromosomeFilter = SexChromosomeFilter.EXCLUDE_XY,
    ) -> List[Shard]:
        """Partitioner selection — PcaConf.getPartitioner
        (GenomicsConf.scala:92-100)."""
        if all_references:
            return shards_for_all_references(
                sex_filter, self.bases_per_partition
            )
        return shards_for_references(
            self.references, self.bases_per_partition
        )


@dataclass
class PcaConfig(GenomicsConfig):
    all_references: bool = False
    debug_datasets: bool = False
    min_allele_frequency: Optional[float] = None
    num_pc: int = 2
    # Cohort sample restriction: `samples` keeps only the named callset
    # ids (None = all), `exclude_samples` then drops ids. Ingest still
    # extracts in the full callset frame; carriers are remapped/dropped
    # at the window boundary, so the Gramian, finish, and emission are
    # sized by the restricted cohort. This is the per-job cohort axis
    # the serving tier's delta/gang paths ride (docs/OPERATIONS.md
    # §4c); meshless uncheckpointed runs only.
    samples: Optional[List[str]] = None
    exclude_samples: Optional[List[str]] = None
    precise: bool = False  # host-f64 eigendecomposition (driver-side LAPACK analog)
    # PCA pipeline route. "auto" (default) runs the fused single-dispatch
    # finish (centering + CholeskyQR subspace eig + row sums in one
    # program, one packed readback — ops/fused.py) on single-host
    # unsharded runs up to --dense-eigh-limit samples, the
    # streamed/dense route everywhere else, and the SPARSE Gramian
    # accumulation (below) on sample-sharded host-local-mesh runs —
    # the biobank shape; "fused" forces the fused finish (errors on
    # configs it cannot serve: --precise, meshes, multi-process);
    # "stream" forces the pre-round-5 dense/randomized route; "sparse"
    # forces sparse-aware Gramian accumulation (ops/sparse.py): G
    # accumulates by OOB-drop scatter straight from CSR carrier
    # windows — no densify, no bit-pack, work O(Σk²) instead of
    # O(N²·V) — 2-D tile-sharded over the mesh when one is configured,
    # finishing through the sharded randomized eig; "sketch" forces the
    # Gramian-FREE engine (ops/sketch.py): the same CSR windows
    # accumulate an (N, k+p) randomized sketch panel instead of any N×N
    # tile — O(N·(k+p)) memory, TSQR + Nyström finish — the
    # million-sample route (auto selects it only where the N² footprint
    # bound would refuse). The allowed set is the PCA_MODES registry
    # above.
    pca_mode: str = "auto"
    # Gramian-free sketch engine knobs (--pca-mode sketch). Oversample
    # p: the panel carries k+p columns through ops/pcoa.
    # randomized_panel_width — the ONE panel-width policy the exact
    # randomized finish shares. Seed: Ω is drawn from a seeded
    # generator, so a run is bit-reproducible for a fixed seed +
    # topology (NOT bit-identical to the exact path — the documented
    # tolerance contract in ops/sketch.py is the correctness bar).
    # Power iterations: extra full streamed passes with Ω ← orth(Y)
    # between them; 0 keeps the single-pass cold-stream discipline,
    # ≥ 2 tightens coordinates toward the top-k tolerance bars.
    sketch_oversample: int = DEFAULT_RANDOMIZED_OVERSAMPLE
    sketch_seed: int = 0
    sketch_power_iters: int = DEFAULT_SKETCH_POWER_ITERS
    # Dense/sparse switch for the sparse-aware Gramian: a window whose
    # carrier density (nnz / (N·V_blk)) is strictly below this scatters
    # straight from CSR; at or above it, it densifies onto the MXU
    # path. Bit-identical either way (integer-exact both routes); the
    # default is the measured crossover with margin (PERFORMANCE.md
    # decision log).
    sparse_density_threshold: float = 0.02
    # Pod-sparse protocol pipeline depth (process-spanning meshes with
    # --pca-mode sparse): how many window slots the sync thread's
    # header/confirm/carrier exchange runs AHEAD of the device scatter,
    # so exchange latency and payload construction hide behind compute.
    # 0 = inline lockstep (the ablation/debug mode); 2 (double
    # buffering) is right unless exchange latency is extreme.
    pod_pipeline_depth: int = 2
    # Pod-sparse gang coalescing: consecutive scatter-route windows
    # merge into one protocol step until their variant-row total
    # reaches this, so tiny windows amortize one exchange instead of
    # paying per-window latency. 0 disables; G is bit-identical at any
    # setting (integer-exact accumulation).
    pod_coalesce_variants: int = 256
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 64  # shards per Gramian snapshot
    # World-size-independent checkpointing (utils/elastic.py): work units
    # over the GLOBAL manifest + self-describing lane snapshots, so resume
    # works on any number of hosts and survivors re-execute a dead host's
    # remaining units — the Spark task re-execution analog.
    elastic_checkpoint: bool = False
    trace_dir: Optional[str] = None  # jax.profiler trace output
    # The 100k-sample stress regime (BASELINE.md config #5): shard the N×N
    # Gramian over the mesh instead of replicating it. None = auto (shard
    # when N exceeds sample_shard_threshold).
    sample_sharded: Optional[bool] = None
    sample_shard_threshold: int = 16384
    # N above which the PCoA eigendecomposition switches from dense eigh
    # to randomized subspace iteration (the sharded-eig path).
    dense_eigh_limit: int = 8192
    # Convergence target of the randomized eig: the mesh finish stops
    # once every top-k Ritz pair's relative residual ‖C·v − λ·v‖/|λ|
    # drops below this (None = its default, 1e-5) and raises if it never
    # does; eigenvector error is then O(tol/gap). The fused finish
    # retries below it (None = 1e-3).
    eig_tol: Optional[float] = None
    # Shard-parallel host ingest workers (fused paths): 0 = auto (core
    # count capped at 16 for shard extraction; min(4, cores) for the
    # packed-block builder stage), 1 = serial. Results are bit-identical
    # at any setting — shard extraction preserves manifest order, and
    # the block builders' completion-order output feeds an
    # order-independent integer accumulation.
    ingest_workers: int = 0
    # Device-feed staging depth (arrays/feed.device_prefetch): how many
    # transferred blocks the double-buffered host→device feed keeps
    # ahead of the accumulating matmul. Sharding-aware — applies to the
    # replicated and host-local-mesh feeds alike (the process-spanning
    # pod stream is collective lockstep and has no host-side depth).
    # Must be >= 1; 2 (double buffering) is right unless block build
    # latency is very bursty.
    prefetch_depth: int = 2
    # Shard arrival order into the Gramian accumulator on the CSR-direct
    # ingest tier: "manifest" preserves exact manifest order (head-of-
    # line blocking, byte-identical block packing — the historical
    # behavior); "completion" feeds shards as their fetch+decode
    # completes, so a slow remote shard never stalls the device behind
    # it. G is bit-identical either way (integer-exact accumulation —
    # pinned by test); only block composition and wall-clock change.
    # Checkpointed modes keep manifest order (snapshot digests cut at
    # manifest positions). "auto" (the default) resolves to completion
    # on a cold-stream run (the streaming cold path exists to remove
    # arrival-order barriers) and manifest everywhere else; an EXPLICIT
    # manifest/completion is always honored, cold or warm.
    ingest_order: str = "auto"
    # Spark-style speculative execution for straggler shards: when the
    # head-of-line extraction runs far past the median, a duplicate
    # attempt races it and the winner's (identical) result is used.
    speculative_ingest: bool = False
    # Fail-stop deadline (seconds) per pod collective phase: a lost peer
    # stalls survivors inside a native collective forever; the watchdog
    # turns that into a loud exit-77 + snapshot resume (utils/watchdog.py).
    # None = disabled.
    collective_timeout: Optional[float] = None
    # Per-shard ingest retry (the driver-side resilience tier): each
    # shard extraction is idempotent, so failed shards re-execute up to
    # this many total attempts, every attempt drawing down the per-shard
    # wall-clock budget below. 1 = the historical fail-fast behavior.
    shard_retries: int = 1
    shard_retry_deadline: Optional[float] = None
    # Unified telemetry artifacts (spark_examples_tpu.obs): Chrome-trace
    # span timeline, Prometheus metrics dump (+ .jsonl snapshot), and the
    # machine-readable run manifest. None = telemetry off (zero hot-path
    # cost).
    trace_out: Optional[str] = None
    metrics_out: Optional[str] = None
    manifest_out: Optional[str] = None
    # Reads-pipeline surface (models/pairhmm.py + the reads examples):
    # readset filter for streamed reads (None/"" = every readset the
    # cohort holds) and the PairHMM scoring knobs. Pairs per batched
    # forward dispatch (partial flush tiles pad to a pow2 bucket);
    # consensus-haplotype context bases scored on each side of a read's
    # alignment; phred-scaled gap-open/gap-extend penalties (GATK
    # defaults Q45/Q10). Per-pair results are independent of batching,
    # so pairhmm_batch changes wall-clock only.
    read_group_set_id: Optional[str] = None
    pairhmm_batch: int = 128
    pairhmm_context: int = 8
    pairhmm_gap_open_phred: float = 45.0
    pairhmm_gap_ext_phred: float = 10.0


def add_genomics_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--bases-per-partition",
        type=int,
        default=DEFAULT_BASES_PER_SHARD,
        help="Partition each reference using a fixed number of bases",
    )
    p.add_argument(
        "--client-secrets",
        default=None,
        help="Credential JSON for network-source auth (interactive "
        "confirmation required, Client.scala:32-41 semantics): either a "
        "pre-exchanged {'token': ...} or a stored OAuth user credential "
        "(client_id + client_secret + refresh_token, exchanged at "
        "startup via the refresh-token grant); offline sources ignore it",
    )
    p.add_argument(
        "--api-url",
        default=None,
        help="Base URL of a Genomics-compatible HTTP service to ingest "
        "from (see the serve-cohort subcommand)",
    )
    p.add_argument(
        "--cache-dir",
        default=None,
        help="Local directory for mirrored remote cohorts (keyed by the "
        "server's /identity digest): repeat runs against the same served "
        "cohort skip the network and hit the warm sidecar tier",
    )
    p.add_argument(
        "--mirror-mode",
        choices=("full", "light"),
        default="full",
        help="With --cache-dir: 'full' mirrors the whole interchange "
        "cohort (every consumer works offline); 'light' downloads only "
        "callsets + the binary CSR sidecar — at all-autosomes scale a "
        "~2.7 GB npz instead of a ~58 GB JSONL, serving the default "
        "fused pca ingest tiers (record-streaming consumers like "
        "--debug-datasets need 'full')",
    )
    p.add_argument(
        "--cold-stream",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="With --cache-dir on a COLD cohort (no completed mirror): "
        "stream wire frames straight into the fetch->decode->build->put "
        "ingest pipeline — the first Gramian step dispatches while later "
        "shards are still on the wire — and write the mirror through in "
        "the background (atomic per-file; a killed run's partial mirror "
        "is reused by the next cold run). --no-cold-stream restores the "
        "phased cold path (download the whole mirror, then ingest). "
        "Warm runs and checkpointed/mesh contracts are unaffected; G is "
        "bit-identical either way",
    )
    p.add_argument(
        "--input-path",
        default=None,
        help="Path to a cohort snapshot or JSONL cohort directory "
        "(replaces the API source)",
    )
    p.add_argument(
        "--num-reduce-partitions",
        type=int,
        default=10,
        help="Accepted for CLI parity (Spark shuffle knob); unused",
    )
    p.add_argument("--output-path", default=None)
    p.add_argument(
        "--references",
        default=BRCA1_REFERENCES,
        help="Comma separated tuples of reference:start:end",
    )
    p.add_argument(
        "--variant-set-id",
        action="append",
        dest="variant_set_ids",
        default=None,
        help="VariantSet id (repeatable for multi-dataset join/merge)",
    )
    p.add_argument(
        "--mesh-shape",
        default=None,
        help="Device mesh, e.g. 'data:4,model:2' (replaces --spark-master)",
    )
    p.add_argument(
        "--block-variants", type=int, default=DEFAULT_BLOCK_VARIANTS
    )
    p.add_argument(
        "--rpc-retries",
        type=int,
        default=GenomicsConfig.rpc_retries,
        help="Total attempts per network request (HTTP/gRPC): transport "
        "errors and infrastructural statuses (429/502/503/504, "
        "Retry-After honored) retry with jittered exponential backoff; "
        "served application errors never do. 1 disables retries",
    )
    p.add_argument(
        "--rpc-retry-deadline",
        type=float,
        default=None,
        help="Wall-clock budget (seconds) per network request that its "
        "attempts draw down; when it runs dry the last error surfaces "
        "even if attempts remain",
    )
    p.add_argument(
        "--breaker-threshold",
        type=int,
        default=GenomicsConfig.breaker_threshold,
        help="Per-endpoint circuit breaker: consecutive retryable "
        "failures before the circuit OPENS and requests shed instantly "
        "instead of burning their attempt budget against a down tier",
    )
    p.add_argument(
        "--breaker-cooldown",
        type=float,
        default=GenomicsConfig.breaker_cooldown,
        help="Seconds an open circuit sheds before admitting a "
        "half-open probe; the probe's success closes it, failure "
        "re-opens and re-arms the cooldown",
    )
    p.add_argument(
        "--grpc-idle-timeout",
        type=float,
        default=GenomicsConfig.grpc_idle_timeout,
        help="Per-read idle deadline (seconds) on gRPC shard streams: "
        "cancels a stream whose peer is connected but delivering "
        "nothing (the wedged-peer case keepalive cannot catch); an "
        "actively-delivering stream never trips it. 0 disables",
    )
    p.add_argument(
        "--fault-plan",
        default=None,
        help="Activate the deterministic fault-injection plane: a JSON "
        "fault plan, inline ('{\"seed\":1,\"rules\":[...]}') or a path "
        "to a file holding one (env "
        "SPARK_EXAMPLES_TPU_FAULT_PLAN works too); see docs/RESILIENCE.md",
    )


def add_pca_flags(p: argparse.ArgumentParser) -> None:
    add_genomics_flags(p)
    p.add_argument(
        "--all-references",
        action="store_true",
        help="Use all the autosomes (overrides --references)",
    )
    p.add_argument("--debug-datasets", action="store_true")
    p.add_argument("--min-allele-frequency", type=float, default=None)
    p.add_argument("--num-pc", type=int, default=2)
    p.add_argument(
        "--samples",
        type=_csv_list,
        default=None,
        help="Comma-separated callset ids restricting the cohort to "
        "exactly these samples (default: every callset of the "
        "variantsets). Ingest stays full-frame; carriers outside the "
        "cohort drop at the window boundary, so results are identical "
        "to a cohort containing only these samples. Meshless "
        "uncheckpointed runs only",
    )
    p.add_argument(
        "--exclude-samples",
        type=_csv_list,
        default=None,
        help="Comma-separated callset ids dropped from the cohort "
        "(applied after --samples); the ±k cohort-tweak axis the "
        "serving tier's delta index resolves incrementally",
    )
    p.add_argument(
        "--precise",
        action="store_true",
        help="Eigendecompose on host in float64 (Breeze/LAPACK analog)",
    )
    p.add_argument(
        "--checkpoint-dir",
        default=None,
        help="Directory for incremental Gramian snapshots (resume support)",
    )
    p.add_argument("--checkpoint-every", type=int, default=64)
    p.add_argument(
        "--elastic-checkpoint",
        action="store_true",
        help="World-size-independent checkpointing: fixed work units over "
        "the GLOBAL manifest with self-describing lane snapshots, so a "
        "crashed or shrunken cluster resumes on ANY number of hosts and "
        "survivors re-execute a dead host's remaining units (the Spark "
        "task re-execution analog). Multi-host runs need --checkpoint-dir "
        "on a shared filesystem; host-local (DP) accumulation regime only",
    )
    p.add_argument(
        "--ingest-workers",
        type=int,
        default=0,
        help="Threads extracting shards AND building packed genotype "
        "blocks concurrently on the host (fused ingest; 0 = auto — one "
        "per core capped at 16 for extraction, min(4, cores) for the "
        "native block builders; 1 = serial; < 0 rejected). Results are "
        "bit-identical at any setting; only wall-clock changes",
    )
    p.add_argument(
        "--prefetch-depth",
        type=int,
        default=PcaConfig.prefetch_depth,
        help="Blocks the double-buffered host→device feed stages ahead "
        "of the accumulating matmul (default 2; must be >= 1). Applies "
        "to the replicated and host-local-mesh feeds alike; the "
        "process-spanning pod stream is collective lockstep and "
        "ignores it",
    )
    p.add_argument(
        "--ingest-order",
        choices=("auto", "manifest", "completion"),
        default=PcaConfig.ingest_order,
        help="Shard arrival order into the Gramian accumulator on the "
        "CSR-direct ingest tier: 'manifest' preserves exact manifest "
        "order; 'completion' feeds shards as their fetch+decode "
        "completes — the remote binary-frame tier's throughput mode, "
        "where a slow shard never stalls the device; 'auto' (default) "
        "picks completion on cold-stream runs and manifest otherwise. "
        "G is bit-identical either way (integer-exact accumulation); "
        "checkpointed runs always use manifest order",
    )
    p.add_argument(
        "--speculative-ingest",
        action="store_true",
        help="Speculatively re-execute straggler shard extractions "
        "(Spark speculation analog): when the head-of-line shard runs "
        "far past the median completed duration, a duplicate attempt "
        "races it on a spare thread and the first identical result "
        "wins; a failed attempt defers to the survivor. Needs "
        "--ingest-workers > 1 (or auto)",
    )
    p.add_argument(
        "--collective-timeout",
        type=float,
        default=None,
        help="Fail-stop deadline (seconds) per pod collective phase: a "
        "lost peer stalls survivors in a native collective forever; with "
        "this set the process exits 77 instead, and relaunching with the "
        "same --checkpoint-dir resumes every host from the last round. "
        "Pod mode arms each synced round; elastic mode arms only the "
        "final partial-G merge, so there the deadline must budget the "
        "whole-run ingest skew between the fastest and slowest host",
    )
    p.add_argument(
        "--shard-retries",
        type=int,
        default=PcaConfig.shard_retries,
        help="Total attempts per ingested shard (fused/checkpointed "
        "ingest tiers): extraction is idempotent, so a failed shard "
        "re-executes with backoff instead of killing the run — results "
        "are identical, only wall-clock changes. 1 = fail fast "
        "(historical behavior)",
    )
    p.add_argument(
        "--shard-retry-deadline",
        type=float,
        default=None,
        help="Per-shard wall-clock budget (seconds) its retry attempts "
        "draw down",
    )
    p.add_argument(
        "--trace-dir",
        default=None,
        help="Write a jax.profiler trace of the run here",
    )
    p.add_argument(
        "--trace-out",
        default=None,
        help="Write a Chrome-trace-event JSON span timeline here "
        "(open in Perfetto: ui.perfetto.dev; host-side stages, RPC "
        "spans, watchdog/retry instant events)",
    )
    p.add_argument(
        "--metrics-out",
        default=None,
        help="Write a Prometheus text-format metrics dump here "
        "(counters/gauges/latency histograms; a .jsonl machine-readable "
        "snapshot is written alongside)",
    )
    p.add_argument(
        "--manifest-out",
        default=None,
        help="Write the machine-readable run manifest JSON here "
        "(config, device topology, stage timings, counters, histogram "
        "summaries — the per-run artifact BENCH rounds diff)",
    )
    p.add_argument(
        "--sample-sharded",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="Shard the N×N Gramian over the mesh (default: auto above "
        "--sample-shard-threshold; --no-sample-sharded forces the "
        "replicated-G path); the 100k-sample stress regime",
    )
    p.add_argument(
        "--sample-shard-threshold", type=int, default=16384
    )
    p.add_argument(
        "--dense-eigh-limit",
        type=int,
        default=8192,
        help="N above which eigendecomposition uses randomized subspace "
        "iteration instead of dense eigh",
    )
    p.add_argument(
        "--pca-mode",
        choices=PCA_MODES,
        default="auto",
        help="PCA pipeline route: 'auto' (default) runs the fused single-"
        "dispatch finish (centering + subspace eig + row sums in one "
        "device program, one readback) on single-host unsharded runs up "
        "to --dense-eigh-limit samples, and the sparse Gramian on "
        "sample-sharded mesh runs — host-local or process-spanning; "
        "'fused' forces the fused finish; 'stream' forces the "
        "dense-eigh/randomized route; 'sparse' forces sparse-aware "
        "Gramian accumulation straight from CSR carrier windows (no "
        "densify/pack, O(nnz-pairs) work, G tile-sharded over the mesh "
        "— the biobank-scale route; a process-spanning mesh runs the "
        "per-window carrier-allgather protocol: ~d*N*V sparse carrier "
        "integers cross hosts per window instead of dense packed "
        "panels); 'sketch' forces the Gramian-FREE randomized sketch "
        "engine (ops/sketch.py): the same CSR windows accumulate an "
        "(N, k+p) panel — no N×N tile anywhere, O(N*(k+p)) memory, "
        "mesh TSQR + Nystrom finish — the million-sample route, "
        "tolerance-pinned against the exact spectrum (see "
        "--sketch-seed); auto only selects it where the N^2 footprint "
        "bound would refuse",
    )
    p.add_argument(
        "--sketch-oversample",
        type=int,
        default=PcaConfig.sketch_oversample,
        help="Sketch-engine panel oversampling p (--pca-mode sketch): "
        "the streamed panel carries k+p columns (via the shared "
        "randomized_panel_width policy, floor p >= 1 so the spectral-"
        "gap check always has a value past k). Larger p tightens the "
        "approximation at O(N*p) memory and per-window FLOP cost; "
        "p >= N-k makes the Nystrom reconstruction exact to roundoff "
        "(the full-rank tolerance regime)",
    )
    p.add_argument(
        "--sketch-seed",
        type=int,
        default=PcaConfig.sketch_seed,
        help="Seed of the sketch engine's Gaussian test matrix "
        "(--pca-mode sketch): a fixed seed + topology reproduces "
        "coordinates bit-for-bit; different seeds agree within the "
        "documented spectrum tolerance (ops/sketch.py), NOT "
        "bit-identically — the sketch path is approximate by design",
    )
    p.add_argument(
        "--sketch-power-iters",
        type=int,
        default=PcaConfig.sketch_power_iters,
        help="Extra full streamed passes of the sketch engine with "
        "Omega <- orth(Y) between them (--pca-mode sketch): 0 "
        "(default) keeps the one-streamed-pass cold-stream "
        "discipline; >= 2 sharpens coordinates to the top-k "
        "tolerance bars on gapped spectra. Each pass re-streams every "
        "CSR window once",
    )
    p.add_argument(
        "--sparse-density-threshold",
        type=float,
        default=PcaConfig.sparse_density_threshold,
        help="Sparse-Gramian dense/sparse switch: windows with carrier "
        "density strictly below this scatter straight from CSR, at or "
        "above it they densify onto the MXU path; results are "
        "bit-identical either way (integer-exact). On a "
        "process-spanning mesh the route is a per-window GLOBAL "
        "decision synced by the carrier-allgather header — hosts whose "
        "same-step windows land on opposite sides of the threshold "
        "fail together (pin the threshold to 0 or large to force one "
        "route on heterogeneous cohorts)",
    )
    p.add_argument(
        "--pod-pipeline-depth",
        type=int,
        default=PcaConfig.pod_pipeline_depth,
        help="Pod-sparse protocol pipeline depth (process-spanning "
        "meshes, --pca-mode sparse): window slots the host-side "
        "header/confirm/carrier exchange runs ahead of the device "
        "scatter, hiding exchange latency and payload construction "
        "behind compute. 0 = inline lockstep (ablation mode); default "
        "2 (double buffering). G is bit-identical at any depth",
    )
    p.add_argument(
        "--pod-coalesce-variants",
        type=int,
        default=PcaConfig.pod_coalesce_variants,
        help="Pod-sparse gang coalescing target: consecutive "
        "scatter-route windows merge into one protocol step until "
        "their variant-row total reaches this, amortizing one "
        "exchange over many tiny windows (tail windows, small "
        "shards). 0 disables coalescing; G is bit-identical at any "
        "setting",
    )
    p.add_argument(
        "--read-group-set-id",
        default=None,
        help="Readset id filter for reads pipelines (pairhmm, "
        "reads-example): only reads of this read group set stream from "
        "the source; default = every readset in the cohort",
    )
    p.add_argument(
        "--pairhmm-batch",
        type=int,
        default=PcaConfig.pairhmm_batch,
        help="Read x haplotype pairs per batched PairHMM forward "
        "dispatch (pow2-bucketed partial tiles; must be >= 1). Per-pair "
        "log-likelihoods are bit-identical at any setting — batching "
        "changes wall-clock only",
    )
    p.add_argument(
        "--pairhmm-context",
        type=int,
        default=PcaConfig.pairhmm_context,
        help="Consensus-haplotype context bases included on each side "
        "of a read's alignment when scoring it (>= 0); the haplotype "
        "window a read is evaluated against is its span plus this "
        "margin",
    )
    p.add_argument(
        "--pairhmm-gap-open-phred",
        type=float,
        default=PcaConfig.pairhmm_gap_open_phred,
        help="Phred-scaled gap-open penalty of the PairHMM transition "
        "model (GATK default 45, i.e. P(open) ~ 3.2e-5); must be > "
        "10*log10(2) ~= 3.01 — at or below it the match "
        "self-transition 1 - 2*10^(-go/10) is non-positive and every "
        "likelihood would be NaN",
    )
    p.add_argument(
        "--pairhmm-gap-ext-phred",
        type=float,
        default=PcaConfig.pairhmm_gap_ext_phred,
        help="Phred-scaled gap-extension penalty of the PairHMM "
        "transition model (GATK default 10, i.e. P(extend) = 0.1); "
        "must be > 0",
    )
    p.add_argument(
        "--eig-tol",
        type=float,
        default=None,
        help="Eigensolver convergence target |Cv - lv|/|l| per top-k "
        "pair; eigenvector error is then O(tol/gap). On the mesh "
        "finish's randomized path (N above --dense-eigh-limit): the "
        "residual it stops on (default 1e-5), raising if 100 "
        "iterations do not reach it. On the fused path (--pca-mode "
        "auto/fused): the residual check-and-retry bar (default 1e-3). "
        "The iteration count used appears in the stage report",
    )


def add_analyze_flags(p: argparse.ArgumentParser) -> None:
    """The serve-cohort analysis-tier surface (serving/): flag defaults
    derive from the serving layer's own constants — one source of
    truth, like the breaker/retry flags above."""
    from spark_examples_tpu.serving.queue import (
        DEFAULT_QUEUE_DEPTH,
        DEFAULT_TENANT_QUOTA,
    )
    from spark_examples_tpu.serving.tier import DEFAULT_RESULT_CACHE

    p.add_argument(
        "--analyze",
        action="store_true",
        help="Serve the multi-tenant analysis job tier: POST /analyze "
        "submits a cohort spec (dataset, references, AF filter, num_pc) "
        "and GET /jobs/<id> polls it; jobs run PCA against the served "
        "cohort on this host's accelerator with admission control, "
        "per-tenant quotas, result caching, and crash-safe resume "
        "(docs/OPERATIONS.md)",
    )
    p.add_argument(
        "--analyze-workers",
        type=int,
        default=1,
        help="Analysis worker threads executing queued jobs (device "
        "phases serialize on one engine lock regardless; extra workers "
        "only overlap host-side work)",
    )
    p.add_argument(
        "--analyze-queue-depth",
        type=int,
        default=DEFAULT_QUEUE_DEPTH,
        help="Bounded analysis queue depth: submissions beyond it shed "
        "with 429 + Retry-After (derived from the retry policy's "
        "backoff over the consecutive-shed streak) instead of queuing "
        "unboundedly",
    )
    p.add_argument(
        "--analyze-tenant-quota",
        type=int,
        default=DEFAULT_TENANT_QUOTA,
        help="Per-tenant in-flight job quota (queued + running): a "
        "tenant at quota sheds with 429 + Retry-After so one greedy "
        "client cannot starve the others",
    )
    p.add_argument(
        "--analyze-journal-dir",
        default=None,
        help="Directory for the crash-safe analysis job journal (plus "
        "per-job Gramian checkpoints): a killed server restarted with "
        "the same directory replays finished jobs into the result "
        "cache and re-queues in-flight ones deterministically; unset = "
        "in-memory only (a crash forgets every job)",
    )
    p.add_argument(
        "--analyze-cache-size",
        type=int,
        default=DEFAULT_RESULT_CACHE,
        help="Result-cache entries kept (LRU), keyed on the cohort "
        "hash + analysis flags: identical submissions are served "
        "without recomputation, across tenants",
    )
    from spark_examples_tpu.serving.deltas import (
        DEFAULT_DELTA_MAX_SAMPLES,
        DEFAULT_GANG_MAX_SAMPLES,
    )

    p.add_argument(
        "--delta-max-samples",
        type=int,
        default=DEFAULT_DELTA_MAX_SAMPLES,
        help="Incremental serving: a submitted cohort whose sample set "
        "differs from a cached ancestor's by at most this many samples "
        "(same variantsets/references/AF filter) is answered by exact "
        "rank-k corrections to the cached Gramian instead of a "
        "from-scratch re-accumulation — bit-identical results, O(k*N) "
        "touch-up instead of O(N*V) ingest; a checksum guard falls "
        "back to cold on any cache doubt (docs/OPERATIONS.md §4c). "
        "0 disables the delta tier",
    )
    from spark_examples_tpu.serving.replica import (
        DEFAULT_HEARTBEAT_S,
        DEFAULT_LEASE_TTL_S,
    )

    p.add_argument(
        "--store-dir",
        default=None,
        help="Shared durable-store directory for replicated serving: "
        "N serve-cohort replicas pointed at the same directory "
        "coordinate through lease-owned jobs (per-replica journals, a "
        "fenced shared job index, shared Gramian checkpoints and delta "
        "write-through), so killing any replica mid-job lets a "
        "survivor resume it bit-identically (docs/OPERATIONS.md "
        "multi-replica runbook). Unset = single-replica local mode; "
        "an unreachable store degrades to the same, never crashes",
    )
    p.add_argument(
        "--replica-id",
        default=None,
        help="Stable identity of this replica in the shared store "
        "(its lease name and journal subdirectory); default is a "
        "generated host-pid-suffix id. Reusing a dead replica's id "
        "resumes its journal; two LIVE replicas must never share one",
    )
    p.add_argument(
        "--replica-lease-ttl",
        type=float,
        default=DEFAULT_LEASE_TTL_S,
        help="Replica lease time-to-live in seconds (> 0): how stale a "
        "peer's heartbeat must be before survivors declare it dead and "
        "adopt its in-flight jobs. Lower = faster failover, higher = "
        "more tolerance for GC/IO pauses before a live replica is "
        "zombied (its late writes are then fenced, not merged)",
    )
    p.add_argument(
        "--replica-heartbeat",
        type=float,
        default=DEFAULT_HEARTBEAT_S,
        help="Replica lease renewal interval in seconds (0 < value < "
        "ttl; ttl/5 to ttl/3 is a sane band): each renewal re-proves "
        "ownership under the fencing token and recovers the store "
        "after degraded spells",
    )
    p.add_argument(
        "--gang-max-samples",
        type=int,
        default=DEFAULT_GANG_MAX_SAMPLES,
        help="Gang batching: queued compatible jobs (same resolved "
        "variantsets/references/AF filter, cohort size at most this) "
        "coalesce into ONE batched Gramian dispatch — cohorts stacked "
        "on a leading batch axis through a vmapped accumulator, one "
        "jit cache entry, per-job results unstacked and journaled "
        "individually (crash-safe replay semantics unchanged; results "
        "bit-identical to serial execution). 0 disables gang batching",
    )


def _config_from_args(cls, args: argparse.Namespace):
    kwargs = {}
    for f in cls.__dataclass_fields__:
        if hasattr(args, f):
            val = getattr(args, f)
            if val is not None or f not in ("variant_set_ids",):
                kwargs[f] = val
    if kwargs.get("variant_set_ids") is None:
        kwargs.pop("variant_set_ids", None)
    return cls(**kwargs)


def genomics_config_from_args(args) -> GenomicsConfig:
    return _config_from_args(GenomicsConfig, args)


def pca_config_from_args(args) -> PcaConfig:
    return _config_from_args(PcaConfig, args)
