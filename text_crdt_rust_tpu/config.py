"""Framework configuration layer (SURVEY §5 config row; r2 verdict A6).

The reference's knobs are compile-time consts and cargo features
(`Cargo.toml:39-41`, `range_tree/mod.rs:29-39`, `split_list/mod.rs:12-13`);
a JAX framework needs runtime configuration. One dataclass per surface,
a single source of defaults, and ``from_args`` parsers so every CLI
(bench, examples) shares the same knobs instead of growing private
argparse forests.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Sequence

# The ONE canonical engine registry (advisor r3: bench.py and
# EngineConfig had drifted apart; VERDICT r5 weak #6: rle-lanes-mixed
# was missing from the choices entirely).  Maps the public engine name
# to its implementing module (relative to this package) and the bench
# configs that exercise it.  Every CLI choice list and bench dispatch
# derives from this dict; ``tests/test_engine_registry.py`` asserts the
# README table and bench.py name no engine outside it.
ENGINE_REGISTRY = {
    # ``fused_steps``: the engine's insert splice accepts FUSED multi-row
    # steps (``rows_per_step`` W > 1, the split-batch prepare for the
    # kevin prepend worst case).  Streams compiled with ``fuse_w`` > 1
    # may only run on engines carrying this flag; every other engine
    # rejects them at build time.
    "rle":             {"module": "ops.rle", "configs": ("northstar", "2", "3"),
                        "fused_steps": True},
    "rle-hbm":         {"module": "ops.rle_hbm", "configs": ("northstar", "kevin"),
                        "fused_steps": True},
    "rle-lanes":       {"module": "ops.rle_lanes", "configs": ("5",),
                        "fused_steps": True},
    "rle-mixed":       {"module": "ops.rle_mixed", "configs": ("4",)},
    # The blocked per-lane mixed engine serves two surfaces: the config
    # 5r streaming replay AND the document server's lane backend
    # (serve/lanes_backend.py carries the blocked state across ticks).
    "rle-lanes-mixed": {"module": "ops.rle_lanes_mixed",
                        "configs": ("5r", "serve", "serve-lanes"),
                        "fused_steps": True,
                        "serve_backend":
                            "serve.lanes_backend:LanesMixedLaneBackend"},
    # The serve batcher's device backends: ``serve_backend`` names the
    # LaneBackend class `serve.batcher.make_lane_backend` constructs —
    # registry-driven dispatch, no hardcoded engine asserts.  The
    # vmapped flat engine is the measured default; rle-lanes-mixed runs
    # the same serve surface at O(NB+K) touched rows/step.
    "flat":            {"module": "ops.flat", "configs": ("serve",),
                        "serve_backend": "serve.batcher:FlatLaneBackend"},
    # One huge doc sharded over the sp axis (bench --config sp).
    "sp-apply":        {"module": "parallel.sp_apply", "configs": ("sp",)},
}
ENGINE_CHOICES = tuple(ENGINE_REGISTRY)

# Bench-row labels that are not registry engine names: variants mapping
# to a registry engine, or host baselines (None) that have no device
# module.  The registry-consistency test walks bench.py and README
# through this map — any NEW label must land here or in the registry.
ENGINE_ROW_ALIASES = {
    "rle-groups": "rle",       # config 3: rle engine, doc-group grid axis
    "rle-hbm-fused": "rle-hbm",  # kevin: fused multi-row prepare steps
    "native-cpp": None,        # host C++ baseline
    "gap-buffer": None,        # text-only rope lower bound
}


def engines_for(config_key: str) -> tuple:
    """Engine names registered as valid for one bench config key —
    bench.py's per-config dispatch derives from the registry instead of
    private literal tuples."""
    return tuple(n for n, spec in ENGINE_REGISTRY.items()
                 if config_key in spec["configs"])


def supports_fused_steps(engine: str) -> bool:
    """True when ``engine`` (registry name or row alias) carries the
    ``fused_steps`` W-row insert splice — the single source the bench
    and compile plumbing consult before compiling with ``fuse_w`` > 1."""
    name = ENGINE_ROW_ALIASES.get(engine, engine)
    if name is None:
        return False
    return bool(ENGINE_REGISTRY.get(name, {}).get("fused_steps", False))


@dataclasses.dataclass
class BatchConfig:
    """Host-side op-compiler knobs (``ops.batch``) — the compile-side
    twin of ``EngineConfig``; CLIs that shape op streams construct one
    (bench's kevin path) so the compile call sites share these
    defaults rather than growing private literal forests.

    ``fuse_w`` is the split-batch prepare width: backwards-contiguous
    insert bursts (the kevin prepend shape) compile into fused
    ``rows_per_step <= fuse_w`` steps — one device step splices the
    whole burst.  Requires a ``fused_steps`` engine and
    ``fuse_w <= block_k // 2 - 1`` (one leaf split must make room for
    a full fused step); 1 disables fusion.
    """

    lmax: int = 16             # insert-chunk width of compiled steps
    dmax: Optional[int] = None  # per-step delete-span bound (None = off)
    fuse_w: int = 1            # fused insert-burst width (1 = unfused)


def lane_block_geometry(capacity: int, block_k: int) -> tuple:
    """Blocked-lanes geometry for a requested per-lane row capacity:
    ``(capacity, NB, NBT)`` with capacity rounded UP to a ``block_k``
    multiple (K is fixed across a stream's chunks; the growing
    per-chunk capacities of configs 5/5r size NB, not K)."""
    cap = ((capacity + block_k - 1) // block_k) * block_k
    nb = cap // block_k
    return cap, nb, max(8, nb)


@dataclasses.dataclass
class EngineConfig:
    """Device-engine knobs shared by the replay engines."""

    engine: str = "rle"        # one of ENGINE_CHOICES
    batch: int = 128           # docs in the lane dim (256 is the measured
    #                            northstar optimum; 512+ exceeds VMEM,
    #                            PERF.md §5)
    block_k: int = 256         # rows per block (rle: RUN rows)
    lanes_block_k: int = 64    # K for the BLOCKED per-lane engines
    #                            (configs 5/5r): small enough that the
    #                            in-block splice is cheap, large enough
    #                            that NB stays a few dozen (PERF.md §9)
    chunk: int = 1024          # ops per grid step (TPU wants %1024)
    capacity: int = 0          # state rows; 0 = per-workload default
    lmax_cap: int = 512        # insert-chunk cap when compiling merged ops
    interpret: bool = False    # pallas interpreter (CPU logic checks)

    def add_args(self, ap: argparse.ArgumentParser) -> None:
        ap.add_argument("--engine", default=self.engine,
                        choices=ENGINE_CHOICES)
        ap.add_argument("--batch", type=int, default=self.batch)
        ap.add_argument("--block-k", type=int, default=self.block_k)
        ap.add_argument("--lanes-block-k", type=int,
                        default=self.lanes_block_k)
        ap.add_argument("--chunk", type=int, default=self.chunk)
        ap.add_argument("--capacity", type=int, default=self.capacity)
        ap.add_argument("--interpret", action="store_true",
                        default=self.interpret)


@dataclasses.dataclass
class MeshConfig:
    """Multi-chip sharding shape (``parallel.make_mesh``)."""

    n_devices: int = 8
    dp: int = 0                # 0 = derive: n_devices // sp
    sp: int = 1                # sequence/span-parallel axis

    def resolved(self) -> tuple:
        dp = self.dp or (self.n_devices // max(self.sp, 1))
        return dp, self.sp


@dataclasses.dataclass
class StreamConfig:
    """Streaming-apply loop (config 5 shape)."""

    resync_every: int = 1      # chunks between host<->device resyncs
    checkpoint_dir: Optional[str] = None


@dataclasses.dataclass
class ServeConfig:
    """The continuous-batching document server (`serve/`).

    ``engine`` must be registered for the ``serve`` bench config in
    ``ENGINE_REGISTRY`` (the batcher's device backend is built per
    engine; ``serve/batcher.make_lane_backend`` validates and raises a
    typed error for names without a serve backend).
    """

    engine: str = "flat"       # registry engine backing the lane batches
    num_shards: int = 2        # device batches (one [B, CAP] doc batch each)
    lanes_per_shard: int = 16  # B — docs resident per shard batch
    lane_capacity: int = 512   # CAP — body rows per lane (flat: chars;
    #                            rle-lanes-mixed: RUN rows)
    order_capacity: int = 1536 # OCAP — by-order log rows per lane
    lanes_block_k: int = 32    # K (rows per block) for the blocked
    #                            rle-lanes-mixed backend; smaller K than
    #                            the config-5/5r replays because serve
    #                            steps are tiny edits and NBT+K is the
    #                            per-step touched-row floor (PERF.md §10).
    #                            32 is the serve-tuned sweep winner
    #                            (perf/serve_k_sweep.json: min touched
    #                            rows/step over K in {8,16,32,64} on the
    #                            loadgen tick trace)
    interpret: Optional[bool] = None  # pallas interpreter for the lanes
    #                            backend (None = auto: on unless on TPU)
    lmax: int = 16             # insert-chunk width of compiled serve
    #                            steps — ALSO the cap on fused typing
    #                            rows (ISSUE 12 satellite, the PR-6
    #                            lever: 8 capped merged typing runs at
    #                            one word).  The pipeline_probe lmax
    #                            sweep on --workload typing: 8 -> 2628
    #                            device steps, 16 -> 2007 (-24%) at
    #                            equal wall, 32 -> 1856 but +25% CPU
    #                            wall from the 4x-wider char columns —
    #                            16 is the shipped winner (PERF.md §17)
    device_prefill: bool = True  # device-resident by-order logs
    #                            (ISSUE 14): the flat backend ships ONLY
    #                            the per-tick prefill scatter as fixed-
    #                            shape padded delta tensors and applies
    #                            it on device (`ops.flat.
    #                            apply_prefill_delta`), instead of
    #                            round-tripping the four full [B, OCAP]
    #                            logs through host numpy every tick
    #                            (`batch.prefill_logs`) — the serve
    #                            tick's last O(state) host cost becomes
    #                            O(ops), and the dispatch edge stops
    #                            reading device state (the hidden sync
    #                            that ate the pipelined overlap under
    #                            real async dispatch).  Logical streams
    #                            and ledger counters are byte-identical
    #                            either way (tests/test_device_prefill
    #                            .py); False = the PR-3 host path
    #                            (loadgen --host-prefill).  Backends
    #                            without device-resident logs (the
    #                            blocked lanes backend prefills only
    #                            ranks, host-side) accept and ignore it
    pipeline_ticks: int = 2    # host/device tick pipelining depth
    #                            (ISSUE 12): 2 = double-buffered — tick
    #                            N+1's drain/fuse/oracle-apply/compile
    #                            (and residency checkpoint I/O) run on
    #                            the host while tick N's device step is
    #                            still in flight, the per-tick
    #                            block_until_ready deferred to ONE
    #                            staged sync point a tick later; 1 =
    #                            the serial PR-3 loop (dispatch ->
    #                            barrier every tick).  Logical streams,
    #                            flow spans and ledger counters are
    #                            byte-identical at any depth — only
    #                            wall time moves (pinned by
    #                            tests/test_serve_pipeline.py).
    #                            Backends opt in via their
    #                            ``max_pipeline_ticks`` (the blocked
    #                            lanes backend trues up exact per-lane
    #                            row counts at its barrier, so it
    #                            stays serial until that true-up is
    #                            pipeline-safe)
    train_ticks: int = 1       # device tick-train length (ISSUE 20):
    #                            T > 1 = the batcher accumulates T
    #                            ticks' fixed-shape op tensors (+ their
    #                            prefill-delta scatters, concatenated)
    #                            and dispatches them as ONE jitted
    #                            lax.scan program (ops.flat.apply_
    #                            train), collapsing T dispatch
    #                            overheads into one; 1 = today's
    #                            one-dispatch-per-tick loop.  Train
    #                            lengths are padded to powers of two
    #                            ({1,2,4,8}) so steady state never
    #                            recompiles, and the compile set stays
    #                            additive (|S buckets| x |T buckets| +
    #                            |scatter buckets|).  Logical streams
    #                            are byte-identical at any length —
    #                            like pipeline_ticks, a pure wall-clock
    #                            knob (pinned by tests/test_serve_
    #                            train.py).  Backends opt in via
    #                            ``max_train_ticks``: the flat backend
    #                            accepts up to 8 on its device-prefill
    #                            path (host prefill needs per-tick host
    #                            log writes, incompatible with
    #                            deferral); the blocked lanes backend
    #                            stays at 1 (barrier true-up)
    sanitize_pipeline: bool = False  # pipeline aliasing sanitizer
    #                            (ISSUE 13): fingerprint (CRC32) the op
    #                            tensors referenced by each in-flight
    #                            tick at dispatch and re-check them at
    #                            the staged sync — a host write racing
    #                            an in-flight device step fails loudly
    #                            naming the tick/shard/array instead of
    #                            corrupting device state (JAX's CPU
    #                            zero-copy conversion can alias the
    #                            host buffers).  Off by default on the
    #                            raw serving path; cheap enough
    #                            (<5% wall, PERF.md §18) to leave on in
    #                            the serve tests and any pipelined
    #                            deployment being debugged
    step_buckets: tuple = (8, 32, 128)  # padded tick step shapes; a tick
    #                            drains at most step_buckets[-1] compiled
    #                            steps per doc so steady-state serving
    #                            cycles a fixed kernel set (no recompiles)
    max_queue_per_doc: int = 256    # admission: pending events per doc
    max_queue_global: int = 8192    # admission: pending events total
    max_txn_len: int = 128          # admission: items per submitted txn —
    #                            must fit step_buckets[-1] so every
    #                            admitted event can apply in one tick
    #                            (DocServer asserts the pair at build)
    rate_capacity: int = 0          # token bucket size per agent (0 = off)
    rate_refill: int = 0            # tokens added per tick per agent
    spool_dir: Optional[str] = None  # eviction checkpoint directory
    journal_dir: Optional[str] = None  # write-ahead op journal (ISSUE
    #                            16): every admitted op is appended to
    #                            per-shard CRC-chained segments here so
    #                            DocServer.recover() can rebuild a
    #                            crashed server byte-identically
    #                            (checkpoint chains + journal-suffix
    #                            replay).  None = journaling off — the
    #                            shipped default for latency benches
    journal_fsync_ticks: int = 1  # fsync cadence on the logical tick
    #                            axis: segments flush every append
    #                            (process-crash durability) and fsync
    #                            at TICK markers every this-many ticks
    #                            (power-loss durability).  1 = every
    #                            tick; the recovery ledger cell prices
    #                            the shipped cadence
    fuse_steps: bool = True    # generalized tick-stream fusion
    #                            (ops.batch.fuse_steps): typing runs /
    #                            sweeps / replaces / remote runs always
    #                            coalesce; W-row bursts additionally on
    #                            fused_steps backends (ISSUE 6)
    fuse_w: int = 8            # burst width cap; effective W is
    #                            min(fuse_w, lanes_block_k // 2 - 1) on
    #                            backends with the W-row splice, 1 on
    #                            the rest (the one-split headroom rule)
    nagle_txns: int = 16       # columnar-wire emission Nagle window
    #                            (ISSUE 12, the §16 latency lever): a
    #                            peer outbox ships once it holds this
    #                            many txns...
    nagle_rounds: int = 4      # ...or has waited this many ticks
    #                            regardless.  The loadgen's flush
    #                            policy reads both (--nagle-txns /
    #                            --nagle-rounds); smaller windows cut
    #                            clean-remote op-age (emission-to-frame
    #                            batching dominates it, PERF.md §16) at
    #                            a bytes/op cost — 16/4 is the
    #                            perf/pipeline_probe.py sweep winner
    #                            (clean-remote p50 13 -> 4 ticks for
    #                            +14% bytes/op at the 200-doc faulted
    #                            shape, PERF.md §17)
    wire_format: str = "columnar"  # TXNS frames the server EMITS
    #                            (request serving): "row" = PR-1 frame
    #                            version 1, "columnar" = the version-2
    #                            per-column delta wire (net/columnar).
    #                            Decode always negotiates on the version
    #                            byte, so mixed-format peers interop.
    ckpt_format: str = "delta"  # eviction checkpoints: "full" = one
    #                            FORMAT_VERSION-3 oracle snapshot per
    #                            evict (O(doc)); "delta" = CRC-chained
    #                            incremental saves (O(ops since last
    #                            save)) with periodic base compaction
    ckpt_compact_ops: int = 4096   # delta chain: fold into a fresh base
    #                            once ops-since-base exceed this
    ckpt_compact_links: int = 16   # ... or the chain grows this long
    # -- observability (ISSUE 8: obs/) --------------------------------------
    trace: bool = True         # logical-clock event tracer (obs/trace):
    #                            default ON — the overhead probe pins it
    #                            <5% of loadgen wall (PERF.md §14)
    trace_ring: int = 512      # flight-recorder ring: last-N events
    trace_path: Optional[str] = None  # stream every event to this JSONL
    #                            file (logical + segregated wall fields)
    trace_rotate_bytes: Optional[int] = None  # size-cap per stream
    #                            segment: the file rolls to <path>.1,
    #                            <path>.2, ... so a long run never grows
    #                            one unbounded JSONL (None = no cap)
    trace_keep: bool = False   # retain the full event list in memory
    #                            (the trace-determinism tests read it
    #                            back via Tracer.logical_bytes)
    flow_sample_mod: int = 16  # per-op provenance spans (ISSUE 11,
    #                            obs/flow): agents whose crc32(name) %
    #                            mod == 0 get END-TO-END flow.* span
    #                            events (emit/frame/reject/buffer/
    #                            ready/apply on the logical tick axis).
    #                            Per-AGENT sampling keeps every sampled
    #                            span complete, so the conservation
    #                            audit is valid at any mod.  1 = track
    #                            everything (audit/ledger runs); 0 =
    #                            off; the 16 default keeps the serve
    #                            path under the PERF.md §14/§16 5% bar
    obs_dir: Optional[str] = None  # post-mortem bundle directory;
    #                            None = $TCR_TRACE_DIR or
    #                            <spool_dir>/obs

    def add_args(self, ap: argparse.ArgumentParser) -> None:
        ap.add_argument("--serve-shards", type=int, default=self.num_shards)
        ap.add_argument("--serve-lanes", type=int,
                        default=self.lanes_per_shard)
        ap.add_argument("--serve-capacity", type=int,
                        default=self.lane_capacity)


@dataclasses.dataclass
class SoakConfig:
    """``examples.soak`` — the `examples/simple.rs:14-49` driver."""

    edits: int = 1_000_000
    seed: int = 7
    oracle_steps: int = 2_000  # per-step differential-oracle prefix
    detailed: bool = False

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None) -> "SoakConfig":
        d = cls()
        ap = argparse.ArgumentParser(description=__doc__)
        ap.add_argument("--edits", type=int, default=d.edits)
        ap.add_argument("--seed", type=int, default=d.seed)
        ap.add_argument("--oracle", type=int, default=d.oracle_steps,
                        dest="oracle_steps",
                        help="per-step-checked oracle prefix (0 = skip)")
        ap.add_argument("--detailed", action="store_true")
        a = ap.parse_args(argv)
        return cls(edits=a.edits, seed=a.seed,
                   oracle_steps=a.oracle_steps, detailed=a.detailed)


@dataclasses.dataclass
class StatsConfig:
    """``examples.stats`` — the `examples/stats.rs:39-73` driver."""

    trace: str = "automerge-paper"
    engine: str = "native"     # native | oracle
    detailed: bool = False

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None) -> "StatsConfig":
        d = cls()
        ap = argparse.ArgumentParser(description=__doc__)
        ap.add_argument("--trace", default=d.trace)
        ap.add_argument("--engine", default=d.engine,
                        choices=("native", "oracle"))
        ap.add_argument("--detailed", action="store_true")
        a = ap.parse_args(argv)
        return cls(trace=a.trace, engine=a.engine, detailed=a.detailed)
