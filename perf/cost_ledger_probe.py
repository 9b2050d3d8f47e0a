"""Deterministic cost-ledger probe (ISSUE 10 tentpole, part 2).

Derives the ``perf/COST_LEDGER.json`` cells at small PINNED
deterministic shapes and (record mode) commits them.  Every cpu-cell
metric is a pure function of the seeded workload — the same
logical-first discipline that makes two same-seed loadgen runs emit
byte-identical traces (PERF.md §14) — so ``bench.py --check-ledger``
can re-derive the cells on any box, wall-clock-free, and fail with a
named per-metric diff on drift.

Cells (kind ``cpu`` — the tier-1 gate re-derives all of them):

- ``serve``        — the small seeded flat-engine loadgen (the
  `test_obs_trace.small_loadgen_run` shape): device steps pre/post
  fusion, recompiles, wire bytes by lane + bytes/op, checkpoint bytes
  per evict kind, admission/codec rejects, trace volume — PLUS the
  static compiled-HLO cost of the flat serve kernel at every step
  bucket (flops / bytes accessed via ``lower().compile()
  .cost_analysis()``, collectives asserted 0 on the single-shard
  serve), generalizing the ``sp`` 124-collectives count to the serve
  engine×bucket grid;
- ``serve-lanes``  — the SAME seeded tick trace replayed through the
  kernel-exact blocked-lanes cost model (``perf/blocked_lanes_sim``):
  touched rows/step blocked vs flat, pass traffic, splits, hint
  misses — the O(NB+K) contract as a committed number (the real
  lanes-backend run costs ~90 s of pallas-interpret compile, so the
  gate replays the flat run's bit-identical compiled streams instead;
  `perf/serve_lanes_r7.json` holds the full-scale proof);
- ``fused-trace``  — ``ops.batch.fuse_steps`` over a pinned
  automerge-paper prefix compiled at the serve lmax: steps in/out,
  rows saved, per-shape fusion counts;
- ``sp``           — the sequence-parallel engine's static ICI cost
  model at a tiny pinned shape: collectives/step by kind off the
  compiled HLO (the 124 = 94 all-reduce + 30 all-gather invariant),
  flops/bytes banded;
- ``flow``         — per-op provenance (ISSUE 11): the same small
  loadgen at FULL flow sampling — span terminal-state census
  (conservation audit asserted green before pinning) and
  op-age-at-apply percentiles in exact logical ticks, the ROADMAP-7
  pipelined-tick before/after latency contract;
- ``recovery``     — durability (ISSUE 16): the pinned post-dispatch
  crash scenario (kill at a seeded tick with the depth-2 pipeline in
  flight, recover from the journal, resume) — byte-identity to the
  uncrashed twin and both crash-boundary conservation audits asserted
  green BEFORE pinning; metrics are the journal byte bill (bytes/op,
  vs the wire bill — the full-input-log floor, PERF.md §21) and the
  replay economy (records / ops / ticks-to-recover, all logical);
- ``flash-crowd``  — one hot doc takes 90% of traffic from a seeded
  tick on (ISSUE 16 satellite): survives at pinned cost — lane
  overflow degrades to the host oracle (counted, never an assert),
  eviction/restore thrash pinned, convergence asserted.

``--device`` (run on the chip) appends the silicon cells — wall
histograms + real-HLO costs on the default backend, plus the flow
cell's device variant (logical ages must reproduce EXACTLY on chip) —
without touching the cpu cells; the gate skips ``kind: device`` cells
on CPU.

Run:  python perf/cost_ledger_probe.py [--out perf/COST_LEDGER.json]
                                       [--cells a,b] [--device]
Check: python bench.py --check-ledger
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The sp cell's virtual mesh needs the host-device count baked in
# before the CPU client initializes (the sp_bench pattern).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from text_crdt_rust_tpu.obs.ledger import (  # noqa: E402
    LEDGER_PATH,
    LEDGER_SCHEMA_VERSION,
    metric,
    validate_ledger,
)

# -- pinned workload shapes ---------------------------------------------------
# Changing ANY of these is a ledger re-record, not a tweak: the
# committed values are only comparable at these exact shapes.

SEED = 7
SMALL_LOADGEN = dict(docs=6, agents_per_doc=2, ticks=6,
                     events_per_tick=12, zipf_alpha=1.1, fault_rate=0.10,
                     local_prob=0.25, seed=SEED)
SERVE_SHAPE = dict(num_shards=1, lanes_per_shard=4)
SERVE_TRAIN_TICKS = 2  # the serve cell rides a depth-2 tick train
#                        (ISSUE 20) so the pinned dispatch metrics are
#                        nontrivial — every OTHER serve metric must
#                        still match the serial record bit for bit
#                        (train length is a wall-clock-only knob)
FUSED_TRACE = "automerge-paper"
FUSED_PATCHES = 4000
from text_crdt_rust_tpu.config import ServeConfig as _ServeConfig  # noqa: E402

FUSED_LMAX = _ServeConfig().lmax  # the ServeConfig default (16 since
#                    the ISSUE-12 typing-lmax sweep) — ONE source of
#                    truth with the HLO cell's backend, so a future
#                    default change re-records both cells together
#                    instead of drifting them apart
FUSED_W = 8
SP_PATCHES = 120
SP_SHARD_ROWS = 64
HLO_BUCKETS = (8, 32)   # ServeConfig.step_buckets prefix (128 adds ~s
#                         of compile for no extra information)
HLO_TOL = 0.5           # HLO costs drift with compiler versions
WALL_TOL = 1.0          # device-cell wall bands (informational)

_COLLECTIVE_RE = re.compile(
    r"all-gather|all_gather|all-reduce|all_reduce|collective-permute|"
    r"collective_permute|all-to-all|all_to_all", re.IGNORECASE)

CPU_CELLS = ("serve", "serve-lanes", "fused-trace", "sp", "flow",
             "recovery", "flash-crowd")

#: The recovery cell's crash shape: two shards (TICK-marker duplication
#: in play) under eviction pressure, killed post-dispatch mid-run.
CHAOS_SHAPE = dict(num_shards=2, lanes_per_shard=2)
CHAOS_CRASH_TICK = 3
#: Flash-crowd shape: lanes far smaller than the crowd's appetite so
#: the hot doc forces overflow-degrade + residency thrash.
FLASH_TICK = 2
FLASH_DOC = 1
FLASH_SHAPE = dict(num_shards=1, lanes_per_shard=2)


def _force_cpu():
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # in-process import after backend init (tier-1 harness)


def _hlo_cost(lowered) -> dict:
    """(collectives, flops, bytes accessed) of one lowered computation
    — compiled text for the collective count, ``cost_analysis()`` for
    flops/bytes (a list of per-computation dicts on some jax versions).
    """
    compiled = lowered.compile()
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    hits = _COLLECTIVE_RE.findall(text)
    kinds = {}
    for h in hits:
        k = h.lower().replace("_", "-")
        kinds[k] = kinds.get(k, 0) + 1
    ca = compiled.cost_analysis()
    d = ca[0] if isinstance(ca, list) else (ca or {})
    return {"collectives": len(hits), "by_kind": kinds,
            "flops": float(d.get("flops", 0.0)),
            "bytes": float(d.get("bytes accessed", 0.0))}


def _hlo_flat_metrics(platform_note: str = "cpu") -> dict:
    """Static compiled-HLO cost of the flat serve kernel at each step
    bucket (lanes/capacities pinned to SERVE_SHAPE's backend)."""
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import flat as F
    from text_crdt_rust_tpu.serve.batcher import FlatLaneBackend

    backend = FlatLaneBackend(lanes=SERVE_SHAPE["lanes_per_shard"],
                              capacity=512, order_capacity=1536,
                              lmax=FUSED_LMAX)
    out = {}
    for s_bkt in HLO_BUCKETS:
        stacked = B.stack_ops(
            [B.pad_ops(B.empty_ops(FUSED_LMAX), s_bkt)
             for _ in range(backend.lanes)])
        lowered = F._apply_ops_batch.lower(backend.docs, stacked,
                                           local_only=False)
        cost = _hlo_cost(lowered)
        out[f"hlo_flat_b{s_bkt}_flops"] = metric(
            cost["flops"], "hlo", tol=HLO_TOL)
        out[f"hlo_flat_b{s_bkt}_bytes"] = metric(
            cost["bytes"], "hlo", tol=HLO_TOL)
        # Single-shard serving must stay collective-free — an exact 0.
        out[f"hlo_flat_b{s_bkt}_collectives"] = metric(
            cost["collectives"], "hlo")
    return out


def cell_serve_pair():
    """ONE seeded small loadgen run feeding two cells: the ``serve``
    logical-cost cell (from the server's registry + the loadgen report)
    and the ``serve-lanes`` touched-rows cell (the run's compiled tick
    streams replayed through the kernel-exact blocked cost model, sims
    re-seeded from the oracle at every residency upload exactly as the
    device backend is)."""
    from blocked_lanes_sim import (
        BlockedLaneSim,
        Counter,
        UnblockedCost,
        _replay_stream,
        _seed_sim_from_oracle,
    )

    from text_crdt_rust_tpu.config import ServeConfig, lane_block_geometry
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    base = ServeConfig()
    K = base.lanes_block_k
    cap_runs, NB, NBT = lane_block_geometry(base.lane_capacity, K)
    OCAP = base.order_capacity

    cfg = ServeConfig(engine="flat", train_ticks=SERVE_TRAIN_TICKS,
                      **SERVE_SHAPE)
    gen = ServeLoadGen(cfg=cfg, **SMALL_LOADGEN)

    c = Counter()
    unb = UnblockedCost(base.lane_capacity)
    sims = {}

    def tap(doc_id, ops):
        sim = sims.get(doc_id)
        if sim is None:
            sim = sims[doc_id] = BlockedLaneSim(K, cap_runs, c, OCAP)
        _replay_stream(sim, unb, c, ops)

    gen.server.batcher.step_trace = tap
    res = gen.server.residency
    for si, backend in enumerate(res.backends):
        def wrap(orig, si):
            def upload(b, oracle, ranks):
                doc_id = res.lane_owner[si][b]
                sim = sims.get(doc_id)
                if sim is None:
                    sim = sims[doc_id] = BlockedLaneSim(
                        K, cap_runs, c, OCAP)
                _seed_sim_from_oracle(sim, oracle)
                orig(b, oracle, ranks)
            return upload
        backend.upload_lane = wrap(backend.upload_lane, si)

    rep = gen.run()
    assert rep["converged"], rep["mismatches"][:4]

    tick = rep["tick_ms"]
    srv = rep["server"]
    wire = rep["wire"]

    m = {
        # steps: the device-step economy of the tick loop.
        "item_ops_applied": metric(rep["item_ops_applied"], "steps"),
        "steps_total": metric(tick["steps_total"], "steps"),
        "steps_prefuse": metric(tick["steps_prefuse"], "steps"),
        "fused_rows_saved": metric(tick["fused_rows_saved"], "steps"),
        "device_ticks": metric(srv.get("device_ticks", 0), "steps"),
        "device_steps_padded": metric(srv.get("device_steps", 0),
                                      "steps"),
        # compile: steady state must cycle a fixed kernel set.
        "device_compiles": metric(srv.get("device_compiles", 0),
                                  "compile"),
        # train (ISSUE 20): the tick-train dispatch economy at the
        # pinned depth-2 train.  Dispatch counts are logical (same-seed
        # deterministic; partial flushes land at seeded residency
        # boundaries), so they pin exactly in the "steps" family —
        # another named-diff guard: a scheduler change that silently
        # flushes trains shows up here as a dispatch regression.
        "device_dispatches": metric(tick.get("device_dispatches", 0),
                                    "steps"),
        "device_dispatches_per_tick": metric(
            tick.get("device_dispatches_per_tick", 0.0), "steps"),
        "train_len": metric(tick.get("train_len", 0.0), "steps"),
        # prefill (ISSUE 14): the device-resident log path's byte
        # economy — scatter-delta bytes vs the full-log round trip the
        # host path would move, the un-padded scatter volume, and the
        # scatter program's own compile count (bounded by the
        # geometric bucket series).  Bytes metrics live in the "wire"
        # (bytes) family and the compile count in "compile" — the
        # existing families cover them, so no METRIC_FAMILIES growth
        # (and no LEDGER_SCHEMA_VERSION bump invalidating committed
        # bench rows).
        "prefill_bytes_per_tick": metric(
            tick.get("prefill_bytes_per_tick", 0.0), "wire"),
        "prefill_bytes_cut_x": metric(
            tick.get("prefill_bytes_cut_x", 0.0), "wire"),
        "prefill_scatter_len": metric(
            tick.get("prefill_scatter_len", 0), "wire"),
        "prefill_scatter_compiles": metric(
            tick.get("prefill_scatter_compiles", 0), "compile"),
        # wire: the replication byte bill by lane.
        "wire_push_bytes": metric(wire["push_bytes"], "wire"),
        "wire_pull_bytes": metric(wire["pull_bytes"], "wire"),
        "wire_ctrl_bytes": metric(wire["ctrl_bytes"], "wire"),
        "wire_txn_bytes": metric(wire["txn_bytes"], "wire"),
        "ops_replicated": metric(wire["ops_replicated"], "wire"),
        "bytes_per_op": metric(wire["bytes_per_op"], "wire"),
        # ckpt: eviction residency costs by kind.
        "evictions": metric(srv.get("evictions", 0), "ckpt"),
        "restores": metric(srv.get("restores", 0), "ckpt"),
        "ckpt_bytes_written": metric(srv.get("ckpt_bytes_written", 0),
                                     "ckpt"),
        "ckpt_saves_delta": metric(srv.get("ckpt_saves_delta", 0),
                                   "ckpt"),
        "ckpt_saves_full": metric(srv.get("ckpt_saves_full", 0), "ckpt"),
        "ckpt_bytes_per_evict_mean": metric(
            srv.get("ckpt_bytes_per_evict_mean", 0.0), "ckpt"),
        # admission: typed-refusal economy under 10% faults.
        "admitted": metric(srv.get("admitted", 0), "admission"),
        "admitted_items": metric(srv.get("admitted_items", 0),
                                 "admission"),
        "rejected_frame_rejected": metric(
            srv.get("rejected_frame_rejected", 0), "admission"),
        "codec_failures": metric(srv.get("obs_failures_codec", 0),
                                 "admission"),
        # trace: event volume + bundle economy (bounded by design).
        "trace_events": metric(rep["obs"]["trace_events"], "trace"),
        "bundles_written": metric(rep["obs"]["bundles_written"],
                                  "trace"),
        "bundles_suppressed": metric(rep["obs"]["bundles_suppressed"],
                                     "trace"),
    }
    # fuse: per-shape counters the tick fusion produced (stable keys —
    # the run is seeded, so the set of nonzero shapes is pinned too).
    for k in sorted(tick):
        if k.startswith("fuse_"):
            m[k] = metric(tick[k], "fuse")
    m.update(_hlo_flat_metrics())

    serve_cell = {
        "kind": "cpu",
        "workload": {**SMALL_LOADGEN, **SERVE_SHAPE, "engine": "flat",
                     "train_ticks": SERVE_TRAIN_TICKS,
                     "wire": cfg.wire_format, "ckpt": cfg.ckpt_format,
                     "hlo_buckets": list(HLO_BUCKETS),
                     "hlo_lanes": SERVE_SHAPE["lanes_per_shard"]},
        "metrics": m,
    }

    steps = max(c.steps, 1)
    lanes_cell = {
        "kind": "cpu",
        "workload": {**SMALL_LOADGEN, **SERVE_SHAPE,
                     "block_k": K, "lane_capacity_runs": cap_runs,
                     "NBT": NBT, "order_capacity": OCAP,
                     "source": "flat-backend tick trace (bit-identical "
                               "streams; lanes-backend re-derivation is "
                               "the ~90s pallas-interpret path — "
                               "perf/serve_lanes_r7.json holds it at "
                               "full scale)"},
        "metrics": {
            "trace_steps": metric(c.steps, "touched-rows"),
            "splits": metric(c.splits, "touched-rows"),
            "hint_misses": metric(c.hint_misses, "touched-rows"),
            "hint_probes": metric(c.hint_probes, "touched-rows"),
            "touched_rows_per_step_flat": metric(
                round(c.unb_touched / steps, 1), "touched-rows"),
            "touched_rows_per_step_blocked": metric(
                round(c.blk_touched / steps, 1), "touched-rows"),
            "touched_rows_ratio": metric(
                round(c.unb_touched / max(c.blk_touched, 1), 2),
                "touched-rows"),
            "pass_traffic_per_step_flat": metric(
                round(c.unb_traffic / steps, 1), "touched-rows"),
            "pass_traffic_per_step_blocked": metric(
                round(c.blk_traffic / steps, 1), "touched-rows"),
            "pass_traffic_ratio": metric(
                round(c.unb_traffic / max(c.blk_traffic, 1), 2),
                "touched-rows"),
        },
    }
    return serve_cell, lanes_cell


def _flow_metrics(rep: dict) -> dict:
    """The ``flow`` family metrics off a loadgen report's flow block:
    span terminal-state census + op-age-at-apply percentiles, ALL exact
    (ages are logical-tick integers — the same-seed determinism that
    pins every other cpu metric pins these).  The audit must be green
    before anything is pinned: a ledger cell recording a leaky run
    would gate the wrong contract."""
    f = rep["flow"]
    assert f["audit_ok"], f["findings"][:4]
    assert f["spans"]["in_flight"] == 0, f
    m = {
        "flow_events": metric(f["flow_events"], "flow"),
        "spans_emitted": metric(f["spans"]["emitted"], "flow"),
        "spans_applied": metric(f["spans"]["applied"], "flow"),
        "spans_rejected": metric(f["spans"]["rejected"], "flow"),
        "spans_in_flight": metric(f["spans"]["in_flight"], "flow"),
        "dup_applies": metric(f["duplicates"], "flow"),
        "applies_device": metric(f["applies"]["device"], "flow"),
        "applies_host": metric(f["applies"]["host"], "flow"),
        "age_p50_ticks": metric(f["ages_ticks"]["p50"], "flow"),
        "age_p99_ticks": metric(f["ages_ticks"]["p99"], "flow"),
        "age_max_ticks": metric(f["ages_ticks"]["max"], "flow"),
    }
    for band, st in f["by_band"].items():
        if st["count"]:
            m[f"age_{band}_p50_ticks"] = metric(st["p50"], "flow")
            m[f"age_{band}_p99_ticks"] = metric(st["p99"], "flow")
    for cls, st in f["by_class"].items():
        if st["count"]:
            key = cls.replace("-", "_")
            m[f"age_{key}_count"] = metric(st["count"], "flow")
            m[f"age_{key}_p50_ticks"] = metric(st["p50"], "flow")
    return m


def cell_flow():
    """The per-op provenance cell (ISSUE 11): the small seeded loadgen
    with FULL flow sampling (``flow_sample_mod=1``) — every emitted
    span tracked end to end, the conservation audit asserted green,
    and the op-age-at-apply distribution pinned in exact logical
    ticks.  This is the before/after latency contract the ROADMAP-7
    pipelined-tick refactor runs against: logical ages must stay
    byte-identical while only wall time moves."""
    from text_crdt_rust_tpu.config import ServeConfig
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    cfg = ServeConfig(engine="flat", flow_sample_mod=1, **SERVE_SHAPE)
    gen = ServeLoadGen(cfg=cfg, **SMALL_LOADGEN)
    rep = gen.run()
    assert rep["converged"], rep["mismatches"][:4]
    return {
        "kind": "cpu",
        "workload": {**SMALL_LOADGEN, **SERVE_SHAPE, "engine": "flat",
                     "flow_sample_mod": 1},
        "metrics": _flow_metrics(rep),
    }


def cell_recovery():
    """Durability cell (ISSUE 16): the pinned post-dispatch crash —
    kill at tick CHAOS_CRASH_TICK with the depth-2 pipeline in flight,
    recover a fresh server by re-executing the journal, resume the
    surviving clients, and require byte-identity to an uncrashed
    same-seed twin plus green crash-boundary conservation audits —
    all asserted BEFORE anything is pinned.

    The byte metrics pin the full-input-log cost model (PERF.md §21):
    ``journal_bytes_per_op`` is floored by the wire txn bytes/op (a
    REC_TXNS body IS the columnar wire frame), and the control-plane
    records (REQUEST/DIGEST/poll trajectory inputs) ride on top — the
    ratio against the wire bill is pinned exactly so any journal-
    format or trajectory-input change shows up as a named diff."""
    from text_crdt_rust_tpu.serve.chaos import run_crash_scenario

    cell = run_crash_scenario(
        "post-dispatch", CHAOS_CRASH_TICK,
        ticks=SMALL_LOADGEN["ticks"] + 3, docs=SMALL_LOADGEN["docs"],
        agents_per_doc=SMALL_LOADGEN["agents_per_doc"],
        events_per_tick=SMALL_LOADGEN["events_per_tick"], seed=SEED,
        fault_rate=SMALL_LOADGEN["fault_rate"], **CHAOS_SHAPE)
    assert cell["identical"], "recovered streams diverged from twin"
    assert cell["converged"] and cell["twin_converged"]
    assert cell["at_recovery_audit"]["audit_ok"], \
        cell["at_recovery_audit"]["findings"]
    assert cell["final_audit"]["audit_ok"], cell["final_audit"]["findings"]
    rec = cell["recover"]
    wire = cell["report"]["wire"]
    jper = cell["journal_bytes_per_op"]
    m = {
        # The replay economy: what recovery re-executed, all logical.
        "journal_records": metric(rec["records"], "recovery"),
        "journal_refusals": metric(rec["refusals"], "recovery"),
        "replayed_ops": metric(rec["ops"], "recovery"),
        "replayed_txns": metric(rec["txns_replayed"], "recovery"),
        "replayed_locals": metric(rec["locals_replayed"], "recovery"),
        "replayed_frames": metric(rec["frames_replayed"], "recovery"),
        "replayed_polls": metric(rec["polls_replayed"], "recovery"),
        "ticks_to_recover": metric(rec["ticks"], "recovery"),
        "docs_readmitted": metric(rec["docs"], "recovery"),
        # The journal byte bill at the crash point (shipped fsync
        # cadence = every tick), against the wire bill of the full run.
        "journal_bytes": metric(cell["journal_bytes"], "recovery"),
        "journal_ops": metric(cell["journal_ops"], "recovery"),
        "journal_bytes_per_op": metric(jper, "recovery"),
        "wire_txn_bytes_per_op": metric(wire["bytes_per_op"], "wire"),
        "journal_vs_wire_txn_x": metric(
            round(jper / wire["bytes_per_op"], 3), "recovery"),
    }
    return {
        "kind": "cpu",
        "workload": {**SMALL_LOADGEN, **CHAOS_SHAPE,
                     "ticks": SMALL_LOADGEN["ticks"] + 3,
                     "phase": "post-dispatch",
                     "crash_tick": CHAOS_CRASH_TICK,
                     "fsync_ticks": 1},
        "metrics": m,
    }


def cell_flash_crowd():
    """Flash-crowd cell (ISSUE 16 satellite): from FLASH_TICK on, 90%
    of every tick's events slam doc FLASH_DOC while the lanes are far
    too small for it — the hot doc must ride the overflow-degrade path
    (host oracle, counted) and thrash eviction/restore, and the run
    must still converge bit-identically.  Pinned so the degrade and
    thrash economy of the hot-doc pathology is a named diff, not a
    flaky incident."""
    from text_crdt_rust_tpu.config import ServeConfig
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    cfg = ServeConfig(engine="flat", lane_capacity=128,
                      order_capacity=256, **FLASH_SHAPE)
    gen = ServeLoadGen(cfg=cfg, **{**SMALL_LOADGEN, "ticks": 10,
                                   "events_per_tick": 24},
                       flash_crowd=(FLASH_TICK, FLASH_DOC))
    rep = gen.run()
    assert rep["converged"], rep["mismatches"][:4]
    c = gen.server.counters
    srv = rep["server"]
    assert c.get("lane_overflow_degraded") > 0, \
        "flash shape never overflowed — the cell tests nothing"
    hot = gen.worlds[FLASH_DOC]
    m = {
        "item_ops_applied": metric(rep["item_ops_applied"], "steps"),
        "hot_doc_chars": metric(len(hot.twin), "steps"),
        "lane_overflow_degraded": metric(
            c.get("lane_overflow_degraded"), "admission"),
        "evictions": metric(srv.get("evictions", 0), "ckpt"),
        "restores": metric(srv.get("restores", 0), "ckpt"),
        "ckpt_bytes_written": metric(srv.get("ckpt_bytes_written", 0),
                                     "ckpt"),
        "rejected_submissions": metric(rep["rejected_submissions"],
                                       "admission"),
        "wire_txn_bytes": metric(rep["wire"]["txn_bytes"], "wire"),
    }
    return {
        "kind": "cpu",
        "workload": {**SMALL_LOADGEN, **FLASH_SHAPE, "ticks": 10,
                     "events_per_tick": 24, "lane_capacity": 128,
                     "order_capacity": 256,
                     "flash_crowd": f"{FLASH_TICK}:{FLASH_DOC}"},
        "metrics": m,
    }


def cell_fused_trace():
    """Generalized step fusion over a pinned real-trace prefix compiled
    at the serve lmax — the ISSUE-6 step economy as exact counters."""
    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.utils.testdata import (
        flatten_patches,
        load_testing_data,
        trace_path,
    )

    patches = flatten_patches(
        load_testing_data(trace_path(FUSED_TRACE)))[:FUSED_PATCHES]
    ops, _ = B.compile_local_patches(patches, lmax=FUSED_LMAX, dmax=None)
    _fused, fs = B.fuse_steps(ops, fuse_w=FUSED_W)
    m = {
        "steps_prefuse": metric(fs.steps_in, "fuse"),
        "steps_fused": metric(fs.steps_out, "fuse"),
        "rows_saved": metric(fs.rows_saved, "fuse"),
        "reduction_x": metric(round(fs.reduction_x, 3), "fuse"),
    }
    for shape, n in sorted(fs.fused.items()):
        m[f"fuse_{shape}"] = metric(n, "fuse")
    return {
        "kind": "cpu",
        "workload": {"trace": FUSED_TRACE, "patches": FUSED_PATCHES,
                     "lmax": FUSED_LMAX, "fuse_w": FUSED_W},
        "metrics": m,
    }


def cell_sp():
    """The sequence-parallel engine's static ICI cost model at a tiny
    pinned shape: collectives/step by kind off the compiled HLO (scan
    body emitted once -> textual occurrences = per-step cost)."""
    import jax.numpy as jnp
    import numpy as np

    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.parallel import make_mesh
    from text_crdt_rust_tpu.parallel.sp_apply import SpDoc
    from text_crdt_rust_tpu.utils.testdata import (
        flatten_patches,
        load_testing_data,
        trace_path,
    )

    patches = flatten_patches(
        load_testing_data(trace_path("automerge-paper")))[:SP_PATCHES]
    merged = B.merge_patches(patches)
    lmax = max([len(p.ins_content) for p in merged] + [1])
    ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
    mesh = make_mesh(n_devices=8, dp=1, sp=8)
    sdoc = SpDoc(mesh, shard_rows=SP_SHARD_ROWS, order_rows=64,
                 auto_reshard=True)
    cols = tuple(
        jnp.asarray(np.asarray(col, dtype=np.uint32).view(np.int32))
        for col in (ops.kind, ops.pos, ops.del_len, ops.del_target,
                    ops.origin_left, ops.origin_right, ops.rank,
                    ops.ins_len, ops.ins_order_start))
    lowered = sdoc._replay.lower(sdoc.ordp, sdoc.lenp, sdoc.rows,
                                 sdoc.oll, sdoc.orl, sdoc.rkl, *cols)
    cost = _hlo_cost(lowered)
    m = {
        "steps": metric(ops.num_steps, "steps"),
        "collectives_per_step": metric(cost["collectives"], "hlo"),
        "hlo_flops": metric(cost["flops"], "hlo", tol=HLO_TOL),
        "hlo_bytes": metric(cost["bytes"], "hlo", tol=HLO_TOL),
    }
    for kind, n in sorted(cost["by_kind"].items()):
        m[f"collectives_{kind.replace('-', '_')}"] = metric(n, "hlo")
    return {
        "kind": "cpu",
        "workload": {"trace": "automerge-paper", "patches": SP_PATCHES,
                     "sp": 8, "shard_rows": SP_SHARD_ROWS,
                     "order_rows": 64},
        "metrics": m,
    }


def cell_serve_device():
    """Silicon cell (``--device``, on the chip): the same small
    loadgen on the DEFAULT jax backend — per-bucket device-step wall
    histograms plus the real-HLO flat-kernel costs.  Wall metrics carry
    wide bands (they gate nothing on CPU; the cell is the committed
    record of what the chip measured)."""
    import jax

    from text_crdt_rust_tpu.config import ServeConfig
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    platform = jax.devices()[0].platform
    cfg = ServeConfig(engine="flat", **SERVE_SHAPE)
    gen = ServeLoadGen(cfg=cfg, **SMALL_LOADGEN)
    rep = gen.run()
    assert rep["converged"], rep["mismatches"][:4]
    srv = rep["server"]
    m = {}
    for key in sorted(srv):
        if key.startswith("device_step_wall_ms_b") and key.rsplit(
                "_", 1)[-1] in ("mean", "p50", "p99"):
            m[key] = metric(srv[key], "wall", tol=WALL_TOL)
    m["tick_wall_ms_p50"] = metric(srv.get("tick_wall_ms_p50", 0.0),
                                   "wall", tol=WALL_TOL)
    m["tick_wall_ms_p99"] = metric(srv.get("tick_wall_ms_p99", 0.0),
                                   "wall", tol=WALL_TOL)
    for name, entry in _hlo_flat_metrics(platform).items():
        m[f"device_{name}"] = entry
    return {
        "kind": "device",
        "workload": {**SMALL_LOADGEN, **SERVE_SHAPE, "engine": "flat",
                     "platform": platform},
        "metrics": m,
    }


def cell_flow_device():
    """Silicon variant of the ``flow`` cell (``--device``): the
    SAME full-sampling loadgen on the default jax backend.  Because op
    ages are logical-tick integers, the chip must reproduce the cpu
    cell's numbers EXACTLY — this cell is the cross-backend proof that
    per-op latency accounting is device-independent — plus the run's
    wall clock as a banded informational metric."""
    import time

    import jax

    from text_crdt_rust_tpu.config import ServeConfig
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    platform = jax.devices()[0].platform
    cfg = ServeConfig(engine="flat", flow_sample_mod=1, **SERVE_SHAPE)
    gen = ServeLoadGen(cfg=cfg, **SMALL_LOADGEN)
    t0 = time.perf_counter()
    rep = gen.run()
    wall = time.perf_counter() - t0
    assert rep["converged"], rep["mismatches"][:4]
    m = _flow_metrics(rep)
    m["run_wall_s"] = metric(round(wall, 3), "wall", tol=WALL_TOL)
    return {
        "kind": "device",
        "workload": {**SMALL_LOADGEN, **SERVE_SHAPE, "engine": "flat",
                     "flow_sample_mod": 1, "platform": platform},
        "metrics": m,
    }


def derive_cells(names=None) -> dict:
    """Derive the named cpu cells (all of them by default).  ``serve``
    and ``serve-lanes`` share one loadgen run, so requesting either
    derives both internally."""
    names = list(names) if names is not None else list(CPU_CELLS)
    unknown = [n for n in names if n not in CPU_CELLS]
    if unknown:
        raise ValueError(f"unknown ledger cells {unknown}; cpu cells "
                         f"are {CPU_CELLS}")
    out = {}
    if "serve" in names or "serve-lanes" in names:
        serve_cell, lanes_cell = cell_serve_pair()
        if "serve" in names:
            out["serve"] = serve_cell
        if "serve-lanes" in names:
            out["serve-lanes"] = lanes_cell
    if "fused-trace" in names:
        out["fused-trace"] = cell_fused_trace()
    if "sp" in names:
        out["sp"] = cell_sp()
    if "flow" in names:
        out["flow"] = cell_flow()
    if "recovery" in names:
        out["recovery"] = cell_recovery()
    if "flash-crowd" in names:
        out["flash-crowd"] = cell_flash_crowd()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=LEDGER_PATH)
    ap.add_argument("--cells", default=None,
                    help="comma-separated cell subset (default: all "
                         "cpu cells)")
    ap.add_argument("--device", action="store_true",
                    help="derive the SILICON cells on the default jax "
                         "backend and merge them into --out, keeping "
                         "the committed cpu cells")
    a = ap.parse_args()

    import jax

    if a.device:
        cells = {"serve-device": cell_serve_device(),
                 "flow-device": cell_flow_device()}
        with open(a.out) as f:
            ledger = json.load(f)
        ledger["cells"].update(cells)
        ledger.setdefault("recorded", {})["device"] = {
            "jax": jax.__version__,
            "platform": jax.devices()[0].platform,
        }
    else:
        _force_cpu()
        want = a.cells.split(",") if a.cells else None
        cells = derive_cells(want)
        prior = {}
        if os.path.exists(a.out):
            with open(a.out) as f:
                prior = json.load(f)
        # A cpu re-record NEVER erases silicon work: prior device cells
        # (and their provenance) always survive.  A full re-record
        # supersedes every cpu cell (stale renamed cells drop); a
        # --cells partial keeps the cpu cells it didn't re-derive.
        merged = {n: c for n, c in prior.get("cells", {}).items()
                  if c.get("kind") == "device" or (want and n not in
                                                   cells)}
        merged.update(cells)
        recorded = dict(prior.get("recorded", {}))
        recorded.update({
            "probe": "perf/cost_ledger_probe.py",
            "jax": jax.__version__,
            "note": "cpu cells are exact logical counters (same-"
                    "seed deterministic, PERF.md §14) except hlo "
                    "metrics, which carry relative tolerance "
                    "bands; re-derive with bench.py --check-ledger",
        })
        ledger = {
            "schema_version": LEDGER_SCHEMA_VERSION,
            "recorded": recorded,
            "cells": merged,
        }
    validate_ledger(ledger)
    with open(a.out, "w") as f:
        json.dump(ledger, f, indent=1, sort_keys=True)
        f.write("\n")
    n_metrics = sum(len(c["metrics"]) for c in cells.values())
    print(f"recorded {len(cells)} cell(s) / {n_metrics} metrics "
          f"into {a.out}", file=sys.stderr)
    print(json.dumps({"cells": sorted(ledger["cells"]),
                      "metrics": n_metrics}))


if __name__ == "__main__":
    main()
