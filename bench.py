"""Benchmark suite: the five BASELINE configs + kevin, on real TPU.

Default run = the NORTH STAR: the full automerge-paper trace
(`benches/yjs.rs:32-49`, final-content asserted) tiled across ``--batch``
identical documents on the RLE run-blocked engine (``ops.rle``), fed the
RLE-merged op stream. ``--config all`` runs the
whole BASELINE.json table and writes it to ``BENCH_ALL.json``:

1. automerge-paper single-doc replay — the CPU reference path (our
   native C++ engine), plus the TPU north-star row.
2. ``random_edits`` workload, identical docs batched in the lane dim.
3. ragged mixed corpus (rustcode + sveltecomponent) — divergent doc
   GROUPS on the rle engine's grid dimension.
4. N-peer concurrent-insert storm (tiebreak-heavy) — remote ops on the
   mixed RLE run engine (``ops.rle_mixed``).
5. streaming apply, delete-heavy, per-doc DIVERGENT streams on the
   per-lane rle engine, warm-started across chunks with checkpoint
   resync.
kevin: 5M single-char prepends (`benches/yjs.rs:51-62`) on the native
   engine AND at full 5M scale on the HBM-state RLE engine (leaf
   splits amortize the prepend worst case; batch 128, origins not
   stored — see cfg_kevin's HBM math).

Every row reports ops/sec/chip, ``mean_step_latency_us`` (wall / device
steps), accounted + measured HBM bytes, slope-fit timing fields (see
``time_run``), an oracle-equality flag, and an EQUAL-WORKLOAD
``vs_baseline`` (the native C++ engine replays the same logical workload
single-core at bench time).

Prints exactly ONE JSON line (the north-star row) on stdout; everything
else goes to stderr / BENCH_ALL.json.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import time
from functools import partial

import jax
import numpy as np

from text_crdt_rust_tpu.ops import batch as B
from text_crdt_rust_tpu.ops import flat as F
from text_crdt_rust_tpu.ops import span_arrays as SA
from text_crdt_rust_tpu.utils.randedit import make_storm, random_patches
from text_crdt_rust_tpu.utils.testdata import (
    TestPatch,
    flatten_patches,
    load_testing_data,
    trace_path,
)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def require_tpu():
    """The chip, found in this process, or a loud failure: a
    measurement path never falls back to the CPU (``--cpu`` is the
    explicit logic check)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py: no TPU found (jax sees "
                         f"{dev.platform}); pass --cpu for the CPU "
                         f"logic check")
    return dev


# -- bench row exporter schema (ISSUE 8 satellite) ----------------------------
# Every non-error row BENCH_ALL.json carries must validate against this
# floor: ``--merge-rows`` and the RowSink refuse shape-drifted rows at
# write time, and ``tests/test_bench_row_schema.py`` validates the
# committed table — so a silent field rename or type drift can't split
# the table into incomparable halves (the scattered-dicts failure mode
# the obs/ registry exists to end).  Extra per-config fields are fine;
# the schema pins the shared floor, not the ceiling.
from text_crdt_rust_tpu.obs.ledger import LEDGER_SCHEMA_VERSION

ROW_SCHEMA_VERSION = 1

# Oldest cost-ledger schema whose row counters still MEAN the same
# thing: ledger v2 only ADDED the "recovery" metric family (ISSUE 16),
# so rows stamped v1 remain valid.  A breaking ledger change (a family
# renamed/removed, a counter redefined) must raise this floor to the
# new version so stale rows are refused again; a re-record stamps rows
# at the current version.
LEDGER_COMPAT_FLOOR = 1

ROW_SCHEMA = {
    "schema_version": (int,),
    # The cost-ledger schema the row was recorded against (ISSUE 10):
    # rows and ledger must agree on what the counters MEAN, so
    # --merge-rows refuses rows stamped by a drifted ledger schema.
    "ledger_version": (int,),
    "cfg_key": (str,),
    "variant": (str,),
    "config": (str,),
    "engine": (str,),
    "metric": (str,),
    "value": (int, float),
    "unit": (str,),
    "batch": (int,),
    "ops": (int,),
    "device_steps": (int,),
    "mean_step_latency_us": (int, float),
    "hbm_bytes_accounted": (int,),
    "hbm_bytes_measured": (int, type(None)),
    "vs_baseline": (int, float, type(None)),
    "baseline_ops_per_sec": (int, float, type(None)),
    "oracle_equal": (bool, type(None)),
}


def validate_row(row: dict) -> None:
    """Raise ``ValueError`` naming every schema violation in one bench
    row. Error placeholder rows (``"error"`` key) are exempt — they
    carry a crash record, not metrics."""
    if "error" in row:
        return
    problems = []
    for field, types in ROW_SCHEMA.items():
        if field not in row:
            problems.append(f"missing field {field!r}")
        elif not isinstance(row[field], types):
            problems.append(
                f"field {field!r} has type "
                f"{type(row[field]).__name__}, wants "
                f"{'/'.join(t.__name__ for t in types)}")
    if not problems and row["schema_version"] != ROW_SCHEMA_VERSION:
        problems.append(
            f"schema_version {row['schema_version']} != "
            f"{ROW_SCHEMA_VERSION} (re-record through this exporter)")
    if not problems and (row["ledger_version"] < LEDGER_COMPAT_FLOOR
                         or row["ledger_version"] > LEDGER_SCHEMA_VERSION):
        problems.append(
            f"ledger_version {row['ledger_version']} outside "
            f"[{LEDGER_COMPAT_FLOOR}, {LEDGER_SCHEMA_VERSION}] (row "
            f"counters were recorded against a drifted cost-ledger "
            f"schema; re-record)")
    if problems:
        raise ValueError(
            f"bench row {row.get('config')!r} violates the exporter "
            f"schema: {'; '.join(problems)}")


class RowSink:
    """Persist bench rows to ``path`` AS THEY COMPLETE (VERDICT r3 next
    #1: a crash mid-suite must not lose finished rows), and support
    ``--resume`` (skip configs whose rows are already recorded clean
    UNDER THE SAME workload-shaping flags — a smoke row must not resume
    into a full-size suite)."""

    def __init__(self, path: str, resume: bool, variant: str):
        self.path = path
        self.variant = variant
        self.rows = []
        self.kept = []  # prior rows of OTHER variants: preserved on
        #                 flush (resuming with different flags must not
        #                 erase the results it can't reuse)
        self.pending = {}  # cfg_key -> superseded same-variant rows,
        #                    dropped only when the key re-records
        self.done_keys = set()
        if resume and os.path.exists(path):
            with open(path) as f:
                prior = json.load(f)
            by_key = {}
            for row in prior:
                by_key.setdefault(row.get("cfg_key"), []).append(row)
            for key, rows in by_key.items():
                if key and all("error" not in r
                               and r.get("variant") == variant
                               for r in rows):
                    self.rows.extend(rows)
                    self.done_keys.add(key)
                else:
                    # Preserve rows this resume can't regenerate (other
                    # variants) unconditionally. Same-variant error/
                    # mixed rows are SUPERSEDED by the re-run, but only
                    # once it actually happens: they stay in the file
                    # (via ``pending``) until add() records their key,
                    # so a crash before that point loses nothing.
                    self.kept.extend(r for r in rows
                                     if r.get("variant") != variant)
                    same = [r for r in rows
                            if r.get("variant") == variant]
                    if key and same:
                        self.pending[key] = same
                    elif same:
                        # Keyless (legacy / hand-edited) same-variant
                        # rows have no cfg_key for add() to supersede:
                        # keep them outright, never silently erase.
                        self.kept.extend(same)
            log(f"resume: {len(self.done_keys)} configs already recorded "
                f"clean in {path}: {sorted(self.done_keys)}; "
                f"{len(self.kept)} other-variant rows preserved; "
                f"{len(self.pending)} same-variant error/mixed configs "
                f"scheduled for re-run (their old rows kept until then)")

    def add(self, key: str, out):
        for row in (out if isinstance(out, list) else [out]):
            row["cfg_key"] = key
            row["variant"] = self.variant
            validate_row(row)  # shape-drifted rows fail at write time
            self.rows.append(row)
        self.pending.pop(key, None)  # the re-run supersedes them now
        self.flush()

    def flush(self):
        tmp = self.path + ".tmp"
        stale = [r for rows in self.pending.values() for r in rows]
        with open(tmp, "w") as f:
            json.dump(self.rows + self.kept + stale, f, indent=1)
        os.replace(tmp, self.path)


def merge_config_rows(path, key, rows, variant, smoke=False):
    """Merge a single-config run's rows into the ``--config all`` table
    (``--merge-rows``): the fresh rows REPLACE every prior row of that
    ``cfg_key`` — supersede-by-re-record, without hand-editing the
    JSON.

    Refuses workload-shape downgrades (RowSink's variant rule, applied
    per key): a --smoke run never overwrites full-size rows, and the
    prior rows' ``config`` labels (which embed the workload scale,
    e.g. ``kevin_tpu_5000000``) must all reappear in the fresh rows —
    so re-records at equal workload supersede freely (including under
    a new engine strategy / variant string), while a shrunken
    ``--kevin-n`` run cannot silently destroy the hours-long silicon
    rows.  Error rows are superseded unconditionally."""
    prior = []
    if os.path.exists(path):
        with open(path) as f:
            prior = json.load(f)
    old = [r for r in prior if r.get("cfg_key") == key]
    old_clean = [r for r in old if "error" not in r]
    if smoke and any("smoke=True" not in (r.get("variant") or "")
                     for r in old_clean):
        raise SystemExit(
            f"--merge-rows refused: {path} holds full-size rows for "
            f"cfg_key {key!r} and this is a --smoke run (drop the rows "
            f"by hand if you really mean to supersede them)")
    # Downgrade guard only: full-size rows must reappear label-for-label;
    # prior SMOKE rows are superseded freely (a full run upgrading over a
    # smoke row is the point of the re-record).
    old_full = [r for r in old_clean
                if "smoke=True" not in (r.get("variant") or "")]
    missing = ({r.get("config") for r in old_full}
               - {r.get("config") for r in rows})
    if missing:
        raise SystemExit(
            f"--merge-rows refused: this run produced no replacement "
            f"for prior {key!r} rows {sorted(missing)} — a different "
            f"workload shape must not silently erase recorded rows "
            f"(drop them by hand to supersede deliberately)")
    for row in rows:
        row["cfg_key"] = key
        row["variant"] = variant
        # Schema gate (ISSUE 8): a shape-drifted single-config re-record
        # must not merge into the table it can no longer be compared to.
        validate_row(row)
    kept = [r for r in prior if r.get("cfg_key") != key]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(kept + rows, f, indent=1)
    os.replace(tmp, path)
    log(f"merged {len(rows)} fresh {key!r} rows into {path} "
        f"(replaced {len(old)} prior)")


def expected_content(patches) -> str:
    s = ""
    for p in patches:
        s = s[:p.pos] + p.ins_content + s[p.pos + p.del_len:]
    return s


# ---------------------------------------------------------------- native --


#: Per-run samples of the last native baseline, keyed by caller-visible
#: denominator — ``make_row`` folds the active entry into its row so the
#: committed artifact carries the spread, not just the headline (VERDICT
#: r4 weak #4: a single best-of-run sample under unknown machine load
#: made vs_baseline swing ±40%).
_BASELINE_STATS: dict = {}


def _baseline_samples(run_once, n_ops: int, reps: int):
    """MEDIAN-of-``reps`` single-core baseline with a load guard.

    Best-of rewarded lucky samples; median is robust to one noisy run
    in either direction.  A high 1-minute loadavg (other work sharing
    the cores) is recorded in the row and warned about rather than
    silently denominating the headline.
    """
    loadavg = os.getloadavg()[0] if hasattr(os, "getloadavg") else -1.0
    ncpu = os.cpu_count() or 1
    if loadavg > ncpu * 0.5:
        log(f"WARNING: loadavg {loadavg:.1f} on {ncpu} cpus while "
            f"measuring the CPU baseline; the denominator may be "
            f"depressed and vs_baseline inflated")
    samples = []
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run_once()
        samples.append(time.perf_counter() - t0)
    med = sorted(samples)[len(samples) // 2]
    ops = n_ops / med
    _BASELINE_STATS.clear()
    _BASELINE_STATS.update({
        "baseline_samples_ops_per_sec": [round(n_ops / s, 1)
                                         for s in samples],
        "baseline_loadavg_1m": round(loadavg, 2),
    })
    return ops, out


def native_replay(patches, reps: int = 5):
    """(ops/s, final_string) of the native C++ engine on a local-edit
    patch list, single core, median of ``reps`` (load-guarded)."""
    from text_crdt_rust_tpu.models.native import NativeListCRDT

    pos = [p.pos for p in patches]
    dels = [p.del_len for p in patches]
    ilens = [len(p.ins_content) for p in patches]
    cps = np.frombuffer(
        "".join(p.ins_content for p in patches).encode("utf-32-le"),
        dtype=np.uint32)

    def run_once():
        doc = NativeListCRDT()
        agent = doc.get_or_create_agent_id("bench")
        doc.replay_trace(agent, pos, dels, ilens, cps)
        return doc

    ops, doc = _baseline_samples(run_once, len(patches), reps)
    return ops, doc.to_string()


def native_remote_replay(txns, reps: int = 5):
    """(char-ops/s, final_string) for a RemoteTxn stream on the native
    engine (hot path #2, `doc.rs:242-348`), single core, median of
    ``reps`` (load-guarded)."""
    from text_crdt_rust_tpu.models.native import NativeListCRDT

    n_ops = sum(sum(getattr(op, "len", len(getattr(op, "ins_content", "")))
                    for op in t.ops) for t in txns)

    def run_once():
        doc = NativeListCRDT()
        for t in txns:
            doc.apply_remote_txn(t)
        return doc

    ops, doc = _baseline_samples(run_once, n_ops, reps)
    return ops, doc.to_string()


# ------------------------------------------------------------------ rows --


def measured_device_bytes():
    """Live device allocation (bytes, reason) from the runtime (VERDICT
    r2 weak #5 / r5 missing #3: report measured memory where the backend
    exposes it, a reason note where it doesn't). One shared
    implementation: ``utils.metrics.measured_hbm_bytes``."""
    from text_crdt_rust_tpu.utils.metrics import measured_hbm_bytes

    return measured_hbm_bytes()


def make_row(config, engine, n_ops, batch, wall, steps, hbm_bytes,
             base_ops, oracle_equal, device=None, **extra):
    """One bench row.  ``device`` is the identity of the process that
    produced the numbers (``utils.metrics.device_identity``); it
    defaults to this process, and a config whose numbers come from a
    CPU-only child passes the child's own."""
    from text_crdt_rust_tpu.utils.metrics import device_identity

    total = n_ops * batch
    ops_per_sec = total / wall
    measured, measured_note = measured_device_bytes()
    row = {
        "schema_version": ROW_SCHEMA_VERSION,
        "ledger_version": LEDGER_SCHEMA_VERSION,
        "config": config,
        "engine": engine,
        "metric": "crdt_ops_per_sec_chip",
        "value": round(ops_per_sec, 1),
        "unit": "ops/s",
        "vs_baseline": round(ops_per_sec / base_ops, 3) if base_ops else None,
        "baseline_ops_per_sec": round(base_ops, 1) if base_ops else None,
        # Honest telemetry: the in-kernel steps are not individually
        # timed, so this is the MEAN step latency (wall / device steps),
        # named as such (r2 verdict weak #5 fix).
        "mean_step_latency_us": round(wall / steps * 1e6, 3),
        "device_steps": int(steps),
        # Fused-step accounting (ISSUE 6), present in EVERY row:
        # ``steps_total`` = device steps actually run (post-fusion),
        # ``steps_fused`` = op rows folded into earlier steps (0 on
        # unfused configs).  Configs that fuse pass the real counts
        # (and the per-shape histogram) via **extra, overriding these.
        "steps_total": int(steps),
        "steps_fused": 0,
        "hbm_bytes_accounted": int(hbm_bytes),
        "hbm_bytes_measured": measured,
        "ops": int(n_ops),
        "batch": int(batch),
        "oracle_equal": bool(oracle_equal),
        **(device or device_identity()),
    }
    if measured is None:
        # null + a reason beats a silently absent stat (VERDICT next #5).
        row["hbm_bytes_measured_note"] = measured_note
    row.update(_BASELINE_STATS)  # sample spread + loadavg of the denominator
    _BASELINE_STATS.clear()  # consume-once: rows without their own
    #                          baseline call must not inherit stale stats
    row.update(extra)
    log(f"[{config}] {ops_per_sec:,.0f} ops/s "
        f"(x{row['vs_baseline']} vs native single-core), "
        f"oracle_equal={oracle_equal}")
    return row


def sync(res):
    # A tiny value download (8 x batch ints) as the completion fence.
    # ROADMAP S1: the first benchmark PR checks this and the slope fit
    # below against plain block_until_ready and keeps the simpler one.
    for r in (res if isinstance(res, list) else [res]):
        np.asarray(r.err)


def time_run(run, reps):
    t0 = time.perf_counter()
    res = run()
    first = time.perf_counter() - t0
    log(f"  first run (incl. compile): {first:.2f}s")
    sync(res)  # drain before timing

    def batch_wall(n):
        t0 = time.perf_counter()
        res = None
        for _ in range(n):
            # Drop the previous dispatch's result reference before
            # enqueuing the next: dispatches stay pipelined (the runtime
            # holds buffers until each completes), but Python no longer
            # pins N result sets live — at kevin scale one set is
            # ~10 GiB and two pinned sets exhaust HBM.
            del res
            res = run()
        sync(res)
        return time.perf_counter() - t0, res

    # Throughput: kernels serialize on the one TensorCore, so the wall of
    # an N-dispatch batch is N*kernel + C, with C the constant host
    # overhead of a dispatch and its sync. A two-point slope removes C;
    # a naive total/reps would fold it in and understate throughput,
    # per-rep syncs would pay C every rep and understate it 2-3x.
    # reps < 4 (deliberately slow worst cases, e.g. kevin) skips the fit
    # and reports the conservative RTT-inclusive wall.
    if reps < 4:
        # Drop the warm-up result BEFORE re-dispatching: at kevin scale
        # one result set is ~10 GiB of HBM planes, and two live sets
        # exhaust the chip.
        del res
        t1, res = batch_wall(reps)
        wall = t1 / reps
        _force(res)
        return res, wall, {
            "slope_fit_runs": None,
            "blocking_run_ms_incl_host_rtt": round(t1 / reps * 1e3, 3),
        }
    n1 = max(2, reps // 4)
    n2 = max(n1 + 4, reps)
    del res  # same two-live-result-sets hazard as the reps < 4 branch
    t1, res = batch_wall(n1)
    del res  # and again between the two fit points
    t2, res = batch_wall(n2)
    wall = (t2 - t1) / (n2 - n1)
    if wall <= 0:  # timing noise swamped the fit; fall back (conservative)
        wall = t2 / n2
    # Latency: blocking dispatch + hard sync, labeled as including the
    # host round-trip (the number a caller awaiting a single batch
    # observes). 5 samples -> p50, the BASELINE.json latency metric.
    samples = []
    for _ in range(5):
        del res
        t0 = time.perf_counter()
        res = run()
        sync(res)
        samples.append(time.perf_counter() - t0)
    _force(res)
    dist = {
        "slope_fit_runs": [n1, n2],
        "host_overhead_ms": round((t1 - n1 * wall) * 1e3, 3),
        "blocking_run_ms_incl_host_rtt": round(samples[0] * 1e3, 3),
        "p50_blocking_run_ms_incl_host_rtt": round(
            sorted(samples)[len(samples) // 2] * 1e3, 3),
    }
    return res, wall, dist


def _force(res):
    if isinstance(res, list):
        for r in res:
            r.check()
    else:
        res.check()


# --------------------------------------------------------------- configs --


def cfg_northstar(args):
    """Full automerge-paper trace x batch identical docs.

    Default engine = ``rle``: the run-blocked VMEM engine consuming the
    RLE-merged op stream (`ops.batch.merge_patches`) — 10,712 device
    steps over ~13k run rows for the 259,778-patch trace. ``vs_baseline``
    stays equal-workload: the native C++ engine replays the ORIGINAL
    per-patch stream, and ``ops`` counts original patches.
    """
    from text_crdt_rust_tpu.config import engines_for, supports_fused_steps
    from text_crdt_rust_tpu.ops import rle as R
    from text_crdt_rust_tpu.ops import rle_hbm as RH

    if args.engine not in engines_for("northstar"):
        raise ValueError(
            f"northstar does not implement engine {args.engine!r} "
            f"(choose one of {engines_for('northstar')})")
    data = load_testing_data(trace_path(args.trace))
    patches = flatten_patches(data)
    if args.patches:
        patches = patches[:args.patches]
    n_ops = len(patches)
    # Default geometry (rle): 512 lanes at the measured-optimum capacity
    # 20,992 (r5 sweep). A user-supplied LARGER --capacity falls back to
    # 256 lanes: 512-lane planes exceed the VMEM budget at 32k+ rows
    # (PERF.md §5).
    _rle_cap = args.capacity or 20992
    batch = args.batch or (
        (512 if _rle_cap <= 20992 else 256)
        if args.engine == "rle" else 128)

    base_ops, base_str = native_replay(patches)
    # Full-trace ground truth is shipped with the corpus; the O(n^2)
    # splice oracle only runs for prefixes (r2 verdict weak #6).
    want = data.end_content if not args.patches else expected_content(patches)
    assert base_str == want

    fstats = None
    merged = B.merge_patches(patches)
    lmax = max([len(p.ins_content) for p in merged] + [1])
    ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
    # Generalized step fusion: fold the shapes the host
    # coalescer cannot reach — replace pairs (delete+insert at one
    # position land as ONE dual-branch step) and backwards insert
    # bursts (W-row fused splices) — on the fused-splice engines.
    # --fuse-w 1 disables; default 8 honors every K's headroom.
    fuse_w = args.fuse_w or 8
    if fuse_w > 1 and supports_fused_steps(args.engine):
        ops, fstats = B.fuse_steps(ops, fuse_w=fuse_w)
    # K=128 x 512 lanes x capacity 20,992 is the measured optimum
    # (r5 sweep, committed as perf/sweep_r4.json — written by
    # perf/sweep_r4.py: 3.80G ops/s vs 2.63G at the old 256x32768);
    # the HBM variant holds 1024+ lanes (verdict item 2's batch bar)
    # and G doc GROUPS multiply the concurrent-document count to the
    # 10k of the north-star statement in ONE kernel launch.
    groups = max(args.groups, 1)
    stream = [ops] * groups if groups > 1 else ops
    if args.engine == "rle-hbm":
        block_k = 512
        capacity = args.capacity or 32768
        capacity = ((capacity + block_k - 1) // block_k) * block_k
        maker = partial(RH.make_replayer_rle_hbm, block_k=block_k)
    else:
        block_k = 128
        capacity = args.capacity or 20992  # RUN rows, not chars
        capacity = ((capacity + block_k - 1) // block_k) * block_k
        maker = partial(R.make_replayer_rle, block_k=block_k)
    log(f"[northstar] {args.trace}[:{n_ops}] -> {ops.num_steps} merged "
        f"steps, capacity {capacity} runs, batch {batch} x {groups} "
        f"group(s), engine {args.engine}")
    run = maker(stream, capacity=capacity, batch=batch,
                chunk=args.chunk, interpret=args.interpret)
    hbm = groups * (2 * capacity * batch * 4
                    + 2 * ops.num_steps * batch * 4)
    if groups > 1:
        def to_flat(ops_, res_list):
            # Verify EVERY group's doc 0 (identical streams).
            docs = [R.rle_to_flat(ops_, r) for r in res_list]
            for d in docs[1:]:
                assert SA.to_string(d) == SA.to_string(docs[0])
            return docs[0]
    else:
        to_flat = R.rle_to_flat
    res, wall, dist = time_run(run, args.reps)
    got = SA.to_string(to_flat(ops, res))
    ok = got == want
    if not ok and not args.lax_check:
        raise AssertionError("northstar replay diverged from string oracle")
    steps = ops.num_steps * max(groups, 1)
    fuse_extra = {}
    if fstats is not None:
        fuse_extra = {"steps_fused": fstats.rows_saved * max(groups, 1),
                      "steps_prefuse": fstats.steps_in * max(groups, 1),
                      "fuse_shapes": dict(fstats.fused),
                      "fuse_w": args.fuse_w or 8}
    return make_row("northstar_automerge_paper_full", args.engine, n_ops,
                    batch * max(groups, 1), wall, steps, hbm, base_ops, ok,
                    reps=args.reps, **fuse_extra, **dist)


def cfg_1_cpu(args):
    """Config 1: single-doc full-trace replay on the CPU reference path,
    plus the text-only rope lower bound (`benches/ropey.rs:12-38`)."""
    from text_crdt_rust_tpu.models.native import rope_replay

    data = load_testing_data(trace_path("automerge-paper"))
    patches = flatten_patches(data)
    base_ops, got = native_replay(patches)
    wall = len(patches) / base_ops
    crdt_row = make_row("config1_automerge_paper_cpu", "native-cpp",
                        len(patches), 1, wall, len(patches), 0, base_ops,
                        got == data.end_content)

    # Pre-convert once: list->ndarray conversion is ~15x the replay
    # itself and must not pollute the timed region.
    pos = np.asarray([p.pos for p in patches], np.uint32)
    dels = np.asarray([p.del_len for p in patches], np.uint32)
    il = np.asarray([len(p.ins_content) for p in patches], np.uint32)
    cps = np.frombuffer("".join(p.ins_content for p in patches)
                        .encode("utf-32-le"), np.uint32)
    _n, content = rope_replay(pos, dels, il, cps)  # warm + verify
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        rope_replay(pos, dels, il, cps, want_content=False)
        best = min(best, time.perf_counter() - t0)
    rope_row = make_row("config1_rope_text_only_lower_bound", "gap-buffer",
                        len(patches), 1, best, len(patches), 0,
                        len(patches) / best, content == data.end_content,
                        note="no CRDT metadata; the bound CRDT rows are "
                             "judged against (benches/ropey.rs)")
    return [crdt_row, rope_row]


def _compile_rle(patches, lmax_cap=512):
    """Merged-stream compile + sim-sized run capacity for the rle engine.
    Long inserts chunk at ``lmax_cap``; the in-kernel append-merge fuses
    the chained chunks back into one device run."""
    from text_crdt_rust_tpu.ops import rle as R

    merged = B.merge_patches(patches)
    lmax = min(max([len(p.ins_content) for p in merged] + [1]), lmax_cap)
    ops, _ = B.compile_local_patches(merged, lmax=lmax, dmax=None)
    peak, _final = R.simulate_run_rows(merged)
    capacity = ((int(peak * 2.5) + 255) // 256) * 256
    return ops, max(capacity, 512)


def cfg_2(args):
    """Config 2: random_edits stream, identical docs in the lane dim.

    Random-position edits barely merge (factor ~1) — this config is the
    fragmentation stress: runs stay short, so it measures the rle
    engine's splice/split machinery, not the merge win.
    """
    from text_crdt_rust_tpu.ops import rle as R

    steps = 2000 if args.smoke else 20000
    # Random edits need ~60k run rows; at >128 lanes the two VMEM planes
    # blow the 110MB budget, so this config pins 128.
    batch = min(args.batch, 128) if args.batch else 128
    patches, content = random_patches(random.Random(42), steps)
    base_ops, base_str = native_replay(patches)
    assert base_str == content

    ops, capacity = _compile_rle(patches)
    run = R.make_replayer_rle(ops, capacity=capacity, batch=batch,
                              block_k=256,
                              chunk=128 if args.smoke else 1024,
                              interpret=args.interpret)
    hbm = 2 * capacity * batch * 4 + 2 * ops.num_steps * batch * 4
    res, wall, dist = time_run(run, args.reps)
    got = SA.to_string(R.rle_to_flat(ops, res))
    return make_row("config2_random_edits_identical_docs", "rle",
                    len(patches), batch, wall, ops.num_steps, hbm,
                    base_ops, got == content, **dist)


def cfg_3(args):
    """Config 3: ragged mixed corpus (rustcode + sveltecomponent) as
    divergent doc groups on the rle engine's grid dimension."""
    from text_crdt_rust_tpu.ops import rle as R

    names = ("rustcode", "sveltecomponent")
    datas = [load_testing_data(trace_path(n)) for n in names]
    all_patches = [flatten_patches(d) for d in datas]
    if args.smoke:
        all_patches = [p[:400] for p in all_patches]
    opses, wants = [], []
    capacity = 512
    for p, d in zip(all_patches, datas):
        ops, cap = _compile_rle(p)
        opses.append(ops)
        capacity = max(capacity, cap)
        wants.append(d.end_content if not args.smoke else
                     expected_content(p))

    base_total = 0.0
    group_stats = {}
    for name, ps, want in zip(names, all_patches, wants):
        ops_s, got = native_replay(ps)
        assert got == want
        base_total += ops_s
        group_stats[name] = dict(_BASELINE_STATS)
    base_avg = base_total / len(all_patches)
    # The row's denominator averages the groups; record EVERY group's
    # sample spread, not just the last call's (consume-once would
    # otherwise leave sveltecomponent's samples beside the averaged
    # denominator — review r5).
    _BASELINE_STATS.clear()
    _BASELINE_STATS["baseline_samples_by_group"] = group_stats

    batch3 = args.batch or 128
    run = R.make_replayer_rle(opses, capacity=capacity,
                              batch=batch3, block_k=256,
                              chunk=128 if args.smoke else 1024,
                              interpret=args.interpret)
    hbm = 2 * len(opses) * capacity * batch3 * 4
    results, wall, dist = time_run(run, args.reps)
    ok = True
    for ops, res, want in zip(opses, results, wants):
        got = SA.to_string(R.rle_to_flat(ops, res))
        ok = ok and (got == want)
    n_ops = sum(len(p) for p in all_patches)
    steps = sum(o.num_steps for o in opses)
    return make_row("config3_ragged_mixed_corpus", "rle-groups", n_ops,
                    batch3, wall, steps, hbm, base_avg, ok,
                    groups=list(names), **dist)


def cfg_4(args):
    """Config 4: N-peer concurrent-insert storm (tiebreak-heavy remote
    ops) on the mixed RLE run engine (`doc.rs:242-348` on run rows —
    the r3 verdict's missing #1)."""
    from text_crdt_rust_tpu.ops import rle as R
    from text_crdt_rust_tpu.ops import rle_mixed as RM

    n_peers, rounds, run_len = (4, 10, 2) if args.smoke else (16, 200, 4)
    txns, receiver = make_storm(n_peers, rounds, run_len, seed=7)
    want = receiver.to_string()
    base_ops, base_str = native_remote_replay(txns)
    assert base_str == want

    table = B.AgentTable(sorted({t.id.agent for t in txns}))
    ops, _ = B.compile_remote_txns(txns, table, lmax=min(16, run_len * 2),
                                   dmax=16)
    total_chars = n_peers * rounds * run_len
    # Suite-wide --engine values cfg_4 doesn't distinguish (rle-hbm,
    # rle-lanes, ...) fall back to the default run engine rather than
    # failing the whole config.
    def run_storm_rle_mixed(config, ops_, want_, n_ops_, base_ops_,
                            batch4, **extra):
        """One rle-mixed storm measurement -> a bench row (shared by the
        insert storm and the delete-heavy variant so the capacity
        heuristic and replayer kwargs cannot drift apart)."""
        # Run capacity: every storm op splices <= 3 rows; 2x headroom.
        block_k = 128
        capacity = ((max(int(ops_.num_steps * 3), 256) + block_k - 1)
                    // block_k) * block_k
        run = RM.make_replayer_rle_mixed(
            ops_, capacity=capacity, batch=batch4, block_k=block_k,
            chunk=128 if args.smoke else 1024, interpret=args.interpret)
        res, wall, dist = time_run(run, args.reps)
        got = SA.to_string(R.rle_to_flat(ops_, res))
        return make_row(config, "rle-mixed", n_ops_, batch4, wall,
                        ops_.num_steps, 2 * capacity * batch4 * 4,
                        base_ops_, got == want_,
                        peers=n_peers, rounds=rounds, **extra, **dist)

    # The run engine's planes (~9.6k rows) fit 512 lanes — and its step
    # cost is dominated by lane-independent sequencing (scalar table
    # reads, lane reductions), so wider batches are nearly free.
    batch4 = args.batch or 128
    row = run_storm_rle_mixed("config4_concurrent_insert_storm", ops,
                              want, total_chars, base_ops, batch4)

    # Delete-heavy remote variant (VERDICT r4 next #3: the remote
    # delete path — fragmentation walk, double deletes — had never
    # been benched): ~35% of peer rounds merge earlier history and
    # delete a cross-peer span instead of inserting.
    dtxns, dreceiver = make_storm(n_peers, rounds, run_len, seed=7,
                                  del_prob=0.35)
    dwant = dreceiver.to_string()
    dbase_ops, dbase_str = native_remote_replay(dtxns)
    assert dbase_str == dwant
    dtable = B.AgentTable(sorted({t.id.agent for t in dtxns}))
    dops, _ = B.compile_remote_txns(dtxns, dtable,
                                    lmax=min(16, run_len * 2),
                                    dmax=None)  # one-pass interval delete
    d_chars = sum(sum(getattr(op, "len",
                              len(getattr(op, "ins_content", "")))
                      for op in t.ops) for t in dtxns)
    drow = run_storm_rle_mixed("config4_delete_heavy_storm", dops,
                               dwant, d_chars, dbase_ops, batch4,
                               del_prob=0.35)
    return [row, drow]


def _stream_loop(runners, resync_every, ckpt_path, state_keys):
    """The config-5 streaming loop shared by the local and remote
    variants: device-resident state chained across chunks, segment
    barriers (a tiny err download as the completion fence), EVERY
    chunk's result check()ed at a barrier (err_ref re-zeroes per run,
    so skipping one would discard its flags), and checkpoint resync OFF
    the timed apply path.  ``state_keys`` names the engine's
    ``state()`` tuple fields for the .npz round-trip.  Returns
    (last_res, wall_s, ckpt_ms, resyncs)."""
    state = None
    wall = 0.0
    ckpt_ms = 0.0
    resyncs = 0
    pending = []
    t0 = time.perf_counter()
    for ci, run in enumerate(runners):
        res = run(state)
        state = res.state()
        pending.append(res)
        if (ci + 1) % resync_every == 0 and ci + 1 < len(runners):
            np.asarray(res.err)
            wall += time.perf_counter() - t0
            tc = time.perf_counter()
            for r_ in pending:
                r_.check()
            pending.clear()
            arrs = [np.asarray(x) for x in res.state()]
            np.savez(ckpt_path, **dict(zip(state_keys, arrs)))
            z = np.load(ckpt_path)
            state = tuple(z[k] for k in state_keys)
            ckpt_ms += (time.perf_counter() - tc) * 1e3
            resyncs += 1
            t0 = time.perf_counter()
    np.asarray(res.err)  # final hard sync closes the last segment
    wall += time.perf_counter() - t0
    for r_ in pending:
        r_.check()
    return res, wall, ckpt_ms, resyncs


def _step_latency_pass(runners, chunk_steps):
    """Per-step latency DISTRIBUTION for the streaming configs (VERDICT
    next #5): one extra warm re-chain with a hard sync per chunk; each
    sample is (blocking chunk wall incl. host RTT) / real steps.  Off
    the timed throughput loop — per-chunk syncs would serialize the
    pipelining the timed loop exists to measure."""
    samples = []
    state = None
    for run, steps in zip(runners, chunk_steps):
        t0 = time.perf_counter()
        res = run(state)
        np.asarray(res.err)
        samples.append((time.perf_counter() - t0) / max(steps, 1) * 1e6)
        state = res.state()
    ss = sorted(samples)
    return {
        "p50_step_latency_us_blocking_incl_rtt":
            round(ss[len(ss) // 2], 3),
        "p99_step_latency_us_blocking_incl_rtt":
            round(ss[min(len(ss) - 1, int(round((len(ss) - 1) * 0.99)))],
                  3),
        "step_latency_chunk_samples_us": [round(s, 3) for s in samples],
    }


def cfg_5(args):
    """Config 5: streaming apply over per-doc DIVERGENT streams,
    delete-heavy, with periodic host<->device checkpoint resync.

    Engine: ``ops.rle_lanes`` — B distinct documents advance one op each
    per kernel step.  Round-4 fixes (VERDICT r3 next #3): lane state is
    DEVICE-RESIDENT across chunks (``LanesResult.state()`` feeds the
    next chunk's ``run(state)`` with no download), chunk dispatches are
    pipelined (async; one hard sync per resync segment), and checkpoint
    save/load runs at ``StreamConfig.resync_every`` cadence OFF the
    timed apply path (reported separately as ``checkpoint_ms``).
    """
    from text_crdt_rust_tpu.config import StreamConfig
    from text_crdt_rust_tpu.ops import rle_lanes as RL

    n_docs = 16 if args.smoke else 2048
    chunks = 3 if args.smoke else 8
    steps_per_chunk = 30 if args.smoke else 100
    stream_cfg = StreamConfig(resync_every=2 if args.smoke else 4)
    rngs = [random.Random(1000 + d) for d in range(n_docs)]
    contents = [""] * n_docs

    def next_chunk():
        streams = []
        for d in range(n_docs):
            patches, content = _continue_patches(
                rngs[d], contents[d], steps_per_chunk, ins_prob=0.45)
            contents[d] = content
            streams.append(patches)
        return streams

    all_chunks = [next_chunk() for _ in range(chunks)]

    # GROWING per-chunk capacity from the engine's row invariant
    # (batch.row_growth_bound: <= 2 rows per compiled step) — early
    # chunks run on planes ~1/4 the final size.  The BLOCKED engine
    # keeps K fixed and grows NB with the capacity (the ISSUE-2 block
    # refactor), so each chunk's descent is over NB block sums + one
    # K-row block instead of the whole plane.  Each distinct capacity
    # compiles its own kernel (one-time, pre-warmed below); warm starts
    # zero-pad planes and tables up.
    from text_crdt_rust_tpu.config import lane_block_geometry
    K5 = args.lanes_block_k
    caps = [max(lane_block_geometry(
                B.row_growth_bound(steps_per_chunk * (c + 1)), K5)[0],
                4 * K5) for c in range(chunks)]
    capacity = caps[-1]

    flat0 = [p for ch in all_chunks for p in ch[0]]
    base_ops, base_str = native_replay(flat0)
    assert base_str == contents[0]

    lmax = max((len(p.ins_content) for ch in all_chunks for ps in ch
                for p in ps), default=1) or 1
    ckpt = os.path.join(tempfile.mkdtemp(prefix="tcr_bench_"), "resync.npz")
    next_orders = [0] * n_docs
    n_ops = 0
    steps = 0
    stacked_all = []
    runners = []
    for streams in all_chunks:
        opses = []
        for d, patches in enumerate(streams):
            ops, next_orders[d] = B.compile_local_patches(
                patches, lmax=lmax, dmax=None,
                start_order=next_orders[d])
            opses.append(ops)
            n_ops += len(patches)
        stacked = B.stack_ops(opses)
        stacked_all.append(stacked)
        steps += stacked.num_steps
        runners.append(RL.make_replayer_lanes_blocked(
            stacked, capacity=caps[len(runners)], block_k=K5, chunk=128,
            interpret=args.interpret))

    # Warm with ONE full untimed streaming pass: each runner from the
    # EMPTY init only warms the chunk kernels — the timed loop also
    # runs ``_grow_state``'s pad ops on each PREVIOUS chunk's shapes,
    # and with growing capacities every chunk boundary is a distinct
    # shape pair whose first compile would otherwise land inside the
    # timed wall (the r5 re-record's 6.07ms/step vs the kernel's real
    # ~0.36ms, perf/cfg5_probe.py).
    wstate = None
    for r in runners:
        wres = r(wstate)
        wstate = wres.state()
    np.asarray(wres.err)

    res, wall, ckpt_ms, resyncs = _stream_loop(
        runners, stream_cfg.resync_every, ckpt,
        ("ordp", "lenp", "nlog", "blkord", "rws", "liv"))
    lat = _step_latency_pass(
        runners, [s.num_steps for s in stacked_all])

    ok = True
    for d in range(0, n_docs, max(1, n_docs // 8)):
        flat = RL.expand_lane(res, d)
        chars = {}
        for stacked in stacked_all:
            ilens = np.asarray(stacked.ins_len)[:, d]
            starts = np.asarray(stacked.ins_order_start)[:, d]
            cps = np.asarray(stacked.chars)[:, d]
            for s in np.nonzero(ilens)[0]:
                il = int(ilens[s])
                st = int(starts[s])
                for j in range(il):
                    chars[st + j] = chr(int(cps[s, j]))
        got = "".join(chars[int(o) - 1] for o in flat if o > 0)
        ok = ok and (got == contents[d])
    hbm = 2 * capacity * n_docs * 4 + 2 * steps * n_docs * 4
    return make_row("config5_streaming_divergent_resync", "rle-lanes",
                    n_ops, 1, wall, steps, hbm, base_ops, ok,
                    docs=n_docs, chunks=chunks, capacity=capacity,
                    layout="blocked", lanes_block_k=K5,
                    checkpoint_ms=round(ckpt_ms, 1), resyncs=resyncs,
                    resync_every=stream_cfg.resync_every, **lat)


class _PeerSynth:
    """Fast single-author CRDT peer: turns local patches into a VALID
    RemoteTxn stream (ids exist, seqs dense, delete targets split per
    seq-contiguous run) without the O(doc) oracle replay cost.  For a
    single author, order == seq; origins are the neighboring LIVE ids —
    any intervening tombstones only shift the integrate cursor across
    invisible chars, so the receiver's CONTENT matches the string sim
    (the oracle cross-check in cfg_5_remote verifies exactly this).
    """

    def __init__(self, agent: str):
        self.agent = agent
        self.ids: list = []   # live char ids (seqs) in doc order
        self.seq = 0

    def _rid(self, seq):
        from text_crdt_rust_tpu.common import RemoteId
        if seq is None:
            return RemoteId("ROOT", 0xFFFFFFFF)
        return RemoteId(self.agent, seq)

    def apply(self, patches):
        """-> RemoteTxns for this patch chunk (one txn per patch)."""
        from text_crdt_rust_tpu.common import (
            RemoteDel, RemoteIns, RemoteTxn)
        out = []
        for p in patches:
            ops = []
            seq0 = self.seq
            if p.del_len:
                victims = self.ids[p.pos: p.pos + p.del_len]
                del self.ids[p.pos: p.pos + p.del_len]
                run_start, run_len = victims[0], 1
                for v in victims[1:]:
                    if v == run_start + run_len:
                        run_len += 1
                    else:
                        ops.append(RemoteDel(self._rid(run_start), run_len))
                        run_start, run_len = v, 1
                ops.append(RemoteDel(self._rid(run_start), run_len))
                self.seq += p.del_len
            if p.ins_content:
                il = len(p.ins_content)
                left = self.ids[p.pos - 1] if p.pos > 0 else None
                right = (self.ids[p.pos]
                         if p.pos < len(self.ids) else None)
                ops.append(RemoteIns(self._rid(left), self._rid(right),
                                     p.ins_content))
                self.ids[p.pos:p.pos] = range(self.seq, self.seq + il)
                self.seq += il
            out.append(RemoteTxn(id=self._rid(seq0), parents=[], ops=ops))
        return out


def cfg_5_remote(args):
    """Config 5, REMOTE variant: per-doc DIVERGENT RemoteTxn streams on
    the unified per-lane mixed engine (``ops.rle_lanes_mixed``) — the
    production sync shape (thousands of different documents, each
    applying its own peer's remote ops, `doc.rs:242-348` per lane), the
    r4 verdict's missing #2.  Delete-heavy, streamed in chunks with
    device-resident state (runs + by-order tables) across chunks and
    checkpoint resync off the timed path.  Streams are single-author
    per doc (no tiebreak storms — that is config 4's axis); ``ops``
    counts CHARS (ins chars + delete targets) to match
    ``native_remote_replay``'s equal-workload denominator.
    """
    from text_crdt_rust_tpu.config import StreamConfig
    from text_crdt_rust_tpu.models.oracle import ListCRDT as Oracle
    from text_crdt_rust_tpu.ops import rle_lanes as RL
    from text_crdt_rust_tpu.ops import rle_lanes_mixed as RLM

    n_docs = 16 if args.smoke else 2048
    chunks = 3 if args.smoke else 8
    steps_per_chunk = 30 if args.smoke else 100
    stream_cfg = StreamConfig(resync_every=2 if args.smoke else 4)
    lmax = 4
    rngs = [random.Random(7000 + d) for d in range(n_docs)]
    contents = [""] * n_docs
    synths = [_PeerSynth(f"peer{d}") for d in range(n_docs)]
    all_txns = [[] for _ in range(n_docs)]

    chunk_txns = []
    for _ in range(chunks):
        per_doc = []
        for d in range(n_docs):
            patches, contents[d] = _continue_patches(
                rngs[d], contents[d], steps_per_chunk, ins_prob=0.45)
            txns = synths[d].apply(patches)
            all_txns[d].extend(txns)
            per_doc.append(txns)
        chunk_txns.append(per_doc)

    base_ops, base_str = native_remote_replay(all_txns[0])
    assert base_str == contents[0], "peer stream does not reproduce " \
        "the string sim (synthesizer bug)"

    tables = [B.AgentTable([f"peer{d}"]) for d in range(n_docs)]
    assigners = [None] * n_docs
    opses_by_chunk = []
    n_char_ops = 0
    for per_doc in chunk_txns:
        opses = []
        for d, txns in enumerate(per_doc):
            ops, assigners[d] = B.compile_remote_txns(
                txns, tables[d], assigner=assigners[d], lmax=lmax,
                dmax=None)  # one-pass interval delete: no chunking
            opses.append(ops)
            n_char_ops += sum(
                sum(getattr(op, "len",
                            len(getattr(op, "ins_content", "")))
                    for op in t.ops) for t in txns)
        opses_by_chunk.append(opses)

    # Equal shapes across chunks -> one compiled kernel per geometry
    # (pad every chunk's stacked stream to the suite-wide max step
    # count; padded steps are exact no-ops).
    stacked_all = [B.stack_ops(o) for o in opses_by_chunk]
    real_steps = [s.num_steps for s in stacked_all]  # pre-padding maxima
    smax = ((max(real_steps) + 127) // 128) * 128
    stacked_all = [jax.tree.map(np.asarray, B.pad_ops(s, smax))
                   for s in stacked_all]

    # GROWING per-chunk capacities (see cfg_5), bounded by COMPILED
    # device steps, not patches: a single <=4-char positional delete can
    # compile into up to 4 KIND_REMOTE_DEL steps (one per target order
    # run, batch.py target_runs), and every device step adds <= 2 rows
    # (batch.row_growth_bound; pre-padding counts — padded no-op steps
    # add no rows).  Blocked layout: K fixed, NB grows with capacity.
    from text_crdt_rust_tpu.config import lane_block_geometry
    K5 = args.lanes_block_k
    cum_steps = np.cumsum(real_steps)
    caps = [max(lane_block_geometry(B.row_growth_bound(int(cs)), K5)[0],
                4 * K5) for cs in cum_steps]
    capacity = caps[-1]
    ocaps = [((lmax * steps_per_chunk * (c + 1) + lmax + 7) // 8) * 8
             for c in range(chunks)]
    ocap = ocaps[-1]
    steps = 0
    runners = []
    for ci, stacked in enumerate(stacked_all):
        steps += stacked.kind.shape[0]
        runners.append(RLM.make_replayer_lanes_mixed_blocked(
            stacked, capacity=caps[ci], block_k=K5,
            order_capacity=ocaps[ci],
            chunk=128, lane_tile=min(256, n_docs),
            interpret=args.interpret))

    # Warm with ONE full untimed streaming pass (see cfg_5: the grow-
    # state pad ops at every distinct chunk-boundary shape pair must
    # compile off the timed path, not just the chunk kernels).
    wstate = None
    for r in runners:
        wres = r(wstate)
        wstate = wres.state()
    np.asarray(wres.err)

    ckpt = os.path.join(tempfile.mkdtemp(prefix="tcr_bench_"), "resync.npz")
    res, wall, ckpt_ms, resyncs = _stream_loop(
        runners, stream_cfg.resync_every, ckpt,
        ("ordp", "lenp", "nlog", "blkord", "rws", "liv", "raw",
         "oll", "orl", "ordblk", "fwd"))
    lat = _step_latency_pass(runners, real_steps)

    ok = True
    for d in range(0, n_docs, max(1, n_docs // 8)):
        oracle = Oracle()
        for t in all_txns[d]:
            oracle.apply_remote_txn(t)
        want_signed = [(-1 if oracle.deleted[i] else 1)
                       * (int(oracle.order[i]) + 1)
                       for i in range(oracle.n)]
        got_signed = RL.expand_lane(res, d).tolist()
        ok = ok and got_signed == want_signed \
            and oracle.to_string() == contents[d]
    hbm = (2 * capacity + 2 * ocap) * n_docs * 4
    return make_row("config5_streaming_remote_divergent",
                    "rle-lanes-mixed", n_char_ops, 1, wall, steps, hbm,
                    base_ops, ok,
                    docs=n_docs, chunks=chunks, capacity=capacity,
                    order_capacity=ocap,
                    layout="blocked", lanes_block_k=K5,
                    checkpoint_ms=round(ckpt_ms, 1), resyncs=resyncs,
                    resync_every=stream_cfg.resync_every, **lat)


def cfg_serve(args):
    """Config serve: the continuous-batching document server under the
    seeded closed-loop load generator (`serve/loadgen.py`) — Zipf doc
    popularity forcing evictions, 10% per-class fault injection on
    remote frames, mixed local/remote traffic.  The row records
    sustained applied item-ops/s, batch fill ratio, eviction/restore
    counts, docs resident vs total, and the p50/p99 admission->applied
    latency; ``oracle_equal`` is the ISSUE-3 acceptance bar (every doc
    bit-identical to its host-oracle twin AND every device lane
    bit-identical to its oracle).  ``--engine`` is wired through the
    registry: any engine with a ``serve`` backend runs the same loop
    (``--engine rle-lanes-mixed`` serves from the blocked O(NB+K)
    kernels; the dedicated ``serve-lanes`` config additionally proves
    flat-twin bit-identity and records the step-cost ratio)."""
    from text_crdt_rust_tpu.config import ServeConfig, engines_for
    from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen

    # Fall back to the ServeConfig default (flat, the measured
    # reference backend) — NOT engines_for("serve")[0], which follows
    # registry dict order and silently flipped when rle-lanes-mixed
    # registered for serve.
    engine = args.engine if args.engine in engines_for("serve") \
        else ServeConfig().engine
    docs, ticks, events = (24, 10, 16) if args.smoke else (200, 60, 48)

    # ISSUE 7: the SAME seeded loadgen on both protocol generations —
    # v1 (row frames per event, full-snapshot evictions) vs v2
    # (windowed doc-multiplexed columnar frames, delta-chain
    # evictions).  The primary row is the v2 run; the v1 run's byte
    # counters ride along as the bytes-per-op comparison.
    reports = {}
    for wire, ckpt in (("row", "full"), ("columnar", "delta")):
        scfg = ServeConfig(engine=engine, num_shards=2, lanes_per_shard=16,
                           wire_format=wire, ckpt_format=ckpt,
                           train_ticks=2)
        gen = ServeLoadGen(docs=docs, agents_per_doc=3, ticks=ticks,
                           events_per_tick=events, zipf_alpha=1.1,
                           fault_rate=0.10, local_prob=0.25, seed=7,
                           cfg=scfg)
        reports[wire] = gen.run()
    report = reports["columnar"]
    row_wire = reports["row"]["wire"]
    col_wire = report["wire"]
    full_evict = reports["row"]["server"].get(
        "ckpt_full_bytes_per_evict_mean", 0.0)
    delta_evict = report["server"].get(
        "ckpt_delta_bytes_per_evict_mean", 0.0)
    srv = report["server"]
    lanes = scfg.num_shards * scfg.lanes_per_shard
    hbm = scfg.num_shards * scfg.lanes_per_shard * (
        scfg.lane_capacity + 4 * scfg.order_capacity) * 4
    return make_row(
        "config_serve_continuous_batching", engine,
        report["item_ops_applied"], 1, report["device_ticks_wall_s"],
        max(srv.get("device_steps", 1), 1), hbm, None,
        report["converged"],
        docs=docs, agents_per_doc=3, ticks=ticks, lanes_total=lanes,
        docs_in_lane=srv["docs_in_lane"],
        docs_host_only=srv["docs_host_only"],
        docs_evicted=srv["docs_evicted"],
        docs_degraded=srv.get("docs_degraded", 0),
        evictions=srv.get("evictions", 0),
        restores=srv.get("restores", 0),
        batch_fill_ratio=srv.get("batch_fill_ratio_mean", 0.0),
        frames_rejected=srv.get("rejected_frame_rejected", 0),
        p50_admission_to_applied_us=report["latency_us"]["p50"],
        p99_admission_to_applied_us=report["latency_us"]["p99"],
        tick_p50_ms=report["tick_ms"]["p50"],
        tick_p99_ms=report["tick_ms"]["p99"],
        steps_fused=report["tick_ms"].get("fused_rows_saved", 0),
        steps_prefuse=report["tick_ms"].get("steps_prefuse", 0),
        ops_per_step=report["tick_ms"].get("ops_per_step", 1.0),
        # ISSUE 8: distribution keys (not just means) + trace counters,
        # all flowing from the server's one MetricsRegistry.
        ops_per_step_p99=report["tick_ms"].get("ops_per_step_p99", 0.0),
        ops_per_step_max=report["tick_ms"].get("ops_per_step_max", 0.0),
        device_compiles=report["obs"]["device_compiles"],
        trace_events=report["obs"]["trace_events"],
        obs_bundles=report["obs"]["bundles_written"],
        # ISSUE 11: per-op provenance ride-along (additive fields — the
        # row schema pins the floor, not the ceiling): spans tracked at
        # the shipped sampling default, the conservation-audit verdict,
        # and op-age-at-apply percentiles in logical ticks.
        flow_spans=(report.get("flow") or {}).get(
            "spans", {}).get("emitted", 0),
        flow_audit_ok=(report.get("flow") or {}).get("audit_ok"),
        flow_age_p50_ticks=(report.get("flow") or {}).get(
            "ages_ticks", {}).get("p50", 0),
        flow_age_p99_ticks=(report.get("flow") or {}).get(
            "ages_ticks", {}).get("p99", 0),
        # ISSUE 12: pipelined-tick + Nagle-window ride-alongs (additive
        # fields): how much device-sync demand the staged sync hid, and
        # the emission window the run shipped under.
        pipeline_ticks=(report.get("pipeline") or {}).get("ticks", 1),
        pipeline_overlap_frac=(report.get("pipeline") or {}).get(
            "overlap_frac", 0.0),
        # ISSUE 14: device-resident prefill ride-alongs (additive
        # fields): whether the run shipped scatter deltas instead of
        # full-log round trips, and the per-tick byte cut.
        device_prefill=(report.get("prefill") or {}).get(
            "device_prefill", False),
        prefill_bytes_per_tick=(report.get("prefill") or {}).get(
            "bytes_per_tick", 0.0),
        prefill_bytes_cut_x=(report.get("prefill") or {}).get(
            "bytes_cut_x", 0.0),
        prefill_scatter_compiles=(report.get("prefill") or {}).get(
            "scatter_compiles", 0),
        # ISSUE 20: tick-train ride-alongs (additive fields): the train
        # length the run shipped under and the realized device-dispatch
        # cut vs the serial one-dispatch-per-tick loop (partial flushes
        # at residency boundaries keep it below the depth ceiling).
        train_ticks=(report.get("train") or {}).get("ticks", 1),
        dispatch_cut_x=(report.get("train") or {}).get(
            "dispatch_cut_x", 1.0),
        nagle_txns=col_wire.get("nagle_txns"),
        nagle_rounds=col_wire.get("nagle_rounds"),
        wire_format=col_wire["format"],
        ckpt_format=report["ckpt"]["format"],
        wire_bytes_total=col_wire["txn_bytes"],
        bytes_per_op=col_wire["bytes_per_op"],
        bytes_per_op_row_wire=row_wire["bytes_per_op"],
        wire_bytes_cut_x=round(
            row_wire["bytes_per_op"] / max(col_wire["bytes_per_op"], 1e-9),
            2),
        ckpt_bytes_per_evict=delta_evict,
        ckpt_bytes_per_evict_full=full_evict,
        ckpt_evict_bytes_cut_x=round(
            full_evict / max(delta_evict, 1e-9), 2) if delta_evict else 0.0,
        row_wire_converged=reports["row"]["converged"],
        fault_rate=0.10, zipf_alpha=1.1,
        note="closed-loop serving: ops/s counts applied CRDT item-ops "
             "end-to-end through admission/causal-buffer/batch ticks, "
             "not raw kernel throughput; no equal-workload native "
             "baseline is defined for the serving loop; byte counters "
             "compare the v2 wire/ckpt run against a same-seed v1 run")


def cfg_serve_lanes(args):
    """Config serve-lanes (ISSUE 4): the continuous-batching document
    server on the BLOCKED ``rle-lanes-mixed`` lane backend, proven two
    ways by ``perf/blocked_lanes_sim.py --serve`` in a subprocess (the
    sp_bench pattern — the probe owns its own jax platform config):
    bit-identity (the same seeded loadgen on the lanes backend AND a
    flat-backend twin, every doc byte-identical across backends and to
    the host oracles) and step cost (the loadgen tick trace replayed
    through the kernel-exact blocked cost model vs the flat engine's
    whole-[CAP]-plane model).

    The child is CPU-only by construction (it forces ``jax_platforms``
    to cpu and never touches the chip), so it can run beside this
    process; its row carries the child's own device identity."""
    cmd = [sys.executable,
           os.path.join("perf", "blocked_lanes_sim.py"), "--serve"]
    if args.smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=5400)
    lines = [ln for ln in r.stdout.strip().splitlines() if ln]
    if r.returncode not in (0, 1) or not lines:
        tail = (r.stderr or "").strip().splitlines()[-3:]
        raise RuntimeError(f"serve-lanes probe failed: {tail}")
    out = json.loads(lines[-1])
    rep = out["per_engine"]["rle-lanes-mixed"]
    w = out["workload"]
    # State bytes per lane: 2 run planes + 4 block tables + fwd + the
    # 3 by-order tables (oll/orl/ordblk), i32 each; geometry comes from
    # the probe's own workload report, not re-stated literals.
    hbm = (w["num_shards"] * w["lanes_per_shard"]
           * (2 * w["lane_capacity"] + 5 * w["NBT"]
              + 3 * w["order_capacity"]) * 4)
    ok = rep["converged"] and out["bit_identical_flat_vs_lanes"]
    return make_row(
        "config_serve_lanes_blocked_backend", "rle-lanes-mixed",
        rep["item_ops_applied"], 1, rep["device_ticks_wall_s"],
        max(rep["device_steps"], 1), hbm, None, ok, device=out["device"],
        docs=w["docs"], ticks=w["ticks"], block_k=w["block_k"],
        nb=w["NB"], bit_identical_flat_twin=out[
            "bit_identical_flat_vs_lanes"],
        touched_rows_per_step_flat=out["touched_rows_per_step"]["flat"],
        touched_rows_per_step_lanes=out["touched_rows_per_step"][
            "lanes_blocked"],
        touched_rows_ratio=out["touched_rows_per_step"]["ratio"],
        pass_traffic_ratio=out["pass_traffic_per_step"]["ratio"],
        splits=out["splits"], hint_misses=out["hint_misses"],
        tick_p50_ms=rep["tick_ms"]["p50"],
        tick_p99_ms=rep["tick_ms"]["p99"],
        steps_fused=rep["tick_ms"].get("fused_rows_saved", 0),
        steps_prefuse=rep["tick_ms"].get("steps_prefuse", 0),
        ops_per_step=rep["tick_ms"].get("ops_per_step", 1.0),
        ops_per_step_p99=rep["tick_ms"].get("ops_per_step_p99", 0.0),
        ops_per_step_max=rep["tick_ms"].get("ops_per_step_max", 0.0),
        device_compiles=(rep.get("obs") or {}).get("device_compiles", 0),
        trace_events=(rep.get("obs") or {}).get("trace_events", 0),
        flow_spans=(rep.get("flow") or {}).get(
            "spans", {}).get("emitted", 0),
        flow_audit_ok=(rep.get("flow") or {}).get("audit_ok"),
        flow_age_p50_ticks=(rep.get("flow") or {}).get(
            "ages_ticks", {}).get("p50", 0),
        flow_age_p99_ticks=(rep.get("flow") or {}).get(
            "ages_ticks", {}).get("p99", 0),
        pipeline_ticks=(rep.get("pipeline") or {}).get("ticks", 1),
        pipeline_overlap_frac=(rep.get("pipeline") or {}).get(
            "overlap_frac", 0.0),
        # ISSUE 14 ride-alongs: the lanes backend's by-order tables are
        # device-resident already (only ranks host-merge), so
        # device_prefill reads False and the byte fields stay 0 — the
        # additive fields keep the serve/serve-lanes rows comparable.
        device_prefill=(rep.get("prefill") or {}).get(
            "device_prefill", False),
        prefill_bytes_per_tick=(rep.get("prefill") or {}).get(
            "bytes_per_tick", 0.0),
        prefill_bytes_cut_x=(rep.get("prefill") or {}).get(
            "bytes_cut_x", 0.0),
        prefill_scatter_compiles=(rep.get("prefill") or {}).get(
            "scatter_compiles", 0),
        nagle_txns=(rep.get("wire") or {}).get("nagle_txns"),
        nagle_rounds=(rep.get("wire") or {}).get("nagle_rounds"),
        p50_admission_to_applied_us=rep["latency_us"]["p50"],
        p99_admission_to_applied_us=rep["latency_us"]["p99"],
        evictions=rep["evictions"], restores=rep["restores"],
        wire_format=(rep.get("wire") or {}).get("format"),
        ckpt_format=(rep.get("ckpt") or {}).get("format"),
        wire_bytes_total=(rep.get("wire") or {}).get("txn_bytes"),
        bytes_per_op=(rep.get("wire") or {}).get("bytes_per_op"),
        ckpt_bytes_per_evict=rep.get("ckpt_delta_bytes_per_evict"),
        note=out["note"])


def cfg_sp(args):
    """Config sp: the sequence-parallel sharded engine (VERDICT r5
    missing #5): automerge-paper replay on ``SpDoc`` at virtual sp=8
    with an explicit collectives-per-op count, plus sp=1 parity vs
    ``ops/rle``.  Runs in a subprocess (`perf/sp_bench.py`) because the
    sp mesh needs the host-platform device count baked in before the
    CPU client initializes.  The child is CPU-only by construction (it
    never touches the chip); its rows carry its own device identity."""
    cmd = [sys.executable, os.path.join("perf", "sp_bench.py")]
    if args.smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        tail = (r.stderr or "").strip().splitlines()[-3:]
        raise RuntimeError(f"sp_bench subprocess failed: {tail}")
    rows = []
    for line in r.stdout.strip().splitlines():
        sub = json.loads(line)
        label = sub.pop("label")
        wall = sub.pop("wall_s")
        n_ops = sub.pop("ops")
        steps = sub.pop("device_steps")
        hbm = sub.pop("hbm_bytes_accounted")
        ok = sub.pop("oracle_equal")
        device = sub.pop("device")
        sub.pop("ops_per_sec", None)  # make_row recomputes the headline
        rows.append(make_row(label, "sp-apply", n_ops, 1, wall, steps,
                             hbm, None, ok, device=device, **sub))
    return rows


def _continue_patches(rng, content, steps, ins_prob):
    """random_patches continued from existing content."""
    patches = []
    for _ in range(steps):
        if not content or rng.random() < ins_prob:
            pos = rng.randint(0, len(content))
            ins = "".join(rng.choice("abcdefgh ")
                          for _ in range(rng.randint(1, 4)))
            patches.append(TestPatch(pos, 0, ins))
            content = content[:pos] + ins + content[pos:]
        else:
            pos = rng.randint(0, len(content) - 1)
            span = min(rng.randint(1, 4), len(content) - pos)
            patches.append(TestPatch(pos, span, ""))
            content = content[:pos] + content[pos + span:]
    return patches, content


def cfg_kevin(args):
    """kevin (`benches/yjs.rs:51-62`): 5M single-char prepends on the
    native engine AND on the HBM-state RLE engine (full scale, VERDICT
    r3 next #5), whose logical-block splits amortize the pure-prepend
    worst case (no global rebalance — the round-2 blocker, PERF.md §3).

    HBM math at 5M prepends: capacity = 5M * 2.1 (splits leave blocks
    half full) ~= 10.5M run rows; 2 planes * 10.5M * 128 lanes * 4 B =
    10.75 GB. The lane dim must be a whole 128-wide tile (Mosaic rejects
    64-lane HBM-plane slices), so batch stays 128 and the per-op origin
    outputs — 5.1 GB on their own at this scale — are dropped via
    ``store_origins=False`` (verification reads final state via
    ``expand_runs``, which never needs them). block_k=2048 keeps the
    logical-block tables at ~5k entries instead of 20k."""
    from text_crdt_rust_tpu.config import BatchConfig, supports_fused_steps
    from text_crdt_rust_tpu.ops import rle as R
    from text_crdt_rust_tpu.ops import rle_hbm as RH

    n_native = 50_000 if args.smoke else 5_000_000
    from text_crdt_rust_tpu.models.native import NativeListCRDT
    pos = np.zeros(n_native, np.uint32)
    dels = np.zeros(n_native, np.uint32)
    il = np.ones(n_native, np.uint32)
    cps = np.full(n_native, ord(" "), np.uint32)

    def kevin_once():
        doc = NativeListCRDT()
        a = doc.get_or_create_agent_id("kevin")
        doc.replay_trace(a, pos, dels, il, cps)
        return doc

    # Median-of-3 (each run is ~3s at 5M; the load guard + recorded
    # samples carry the round-5 baseline policy, see _baseline_samples).
    cpu_ops, doc = _baseline_samples(kevin_once, n_native,
                                     1 if args.smoke else 3)
    cpu_row = make_row(f"kevin_cpu_{n_native}", "native-cpp", n_native, 1,
                       n_native / cpu_ops, n_native, 0, cpu_ops,
                       len(doc) == n_native)

    n_tpu = 2048 if args.smoke else args.kevin_n
    patches = [TestPatch(0, 0, " ")] * n_tpu
    # Split-batch prepare (ISSUE 5): the whole workload is ONE
    # backwards-contiguous burst, so at width W the 5M prepends compile
    # to ~5M/W fused multi-row steps — the per-character device-step
    # tax (the last 4x to the 100x bar) gone at the compile stage.
    # W must honor the engines' one-split headroom (W <= K//2 - 1).
    bc = BatchConfig(fuse_w=args.fuse_w or (8 if args.smoke else 64))
    bc.lmax = max(bc.fuse_w, 1)  # single-char bursts: W rows of L=1
    assert supports_fused_steps("rle-hbm") or bc.fuse_w == 1
    ops, _ = B.compile_local_patches(patches, lmax=bc.lmax,
                                     dmax=bc.dmax, fuse_w=bc.fuse_w)
    fuse_w = bc.fuse_w
    # One run row per prepend (runs cannot merge backwards); splits leave
    # blocks half full, so size ~2.1x rows.
    big = n_tpu > 2_000_000
    block_k = 64 if args.smoke else (2048 if big else 512)
    capacity = ((int(n_tpu * 2.1) + block_k - 1) // block_k) * block_k
    batchk = args.batch or 128
    run = RH.make_replayer_rle_hbm(ops, capacity=capacity,
                                   batch=batchk, block_k=block_k,
                                   chunk=128 if args.smoke else 1024,
                                   interpret=args.interpret,
                                   store_origins=not big)
    res, wall, dist = time_run(run, 1)
    flat = R.expand_runs(res)
    got_len = len(flat)
    # Prepends reverse insertion order: orders must read N-1..0.
    order_ok = got_len == n_tpu and bool(
        (flat == np.arange(n_tpu, 0, -1, dtype=np.int32)).all())
    label = "rle-hbm-fused" if fuse_w > 1 else "rle-hbm"
    tpu_row = make_row(f"kevin_tpu_{n_tpu}", label, n_tpu, batchk,
                       wall, ops.num_steps,
                       2 * capacity * batchk * 4,
                       cpu_ops, got_len == n_tpu and order_ok,
                       fuse_w=fuse_w,
                       steps_fused=n_tpu - ops.num_steps,
                       steps_prefuse=n_tpu,
                       fuse_shapes={"burst": n_tpu - ops.num_steps},
                       **dist)
    return [cpu_row, tpu_row]


# ---------------------------------------------------------- ledger gate --


def run_ledger_check(args) -> int:
    """``--check-ledger`` (ISSUE 10): re-derive the committed cost
    ledger's cpu cells at their pinned shapes and fail with a NAMED
    per-metric diff on drift.  Wall-clock-free: every gated metric is a
    logical counter (same-seed deterministic) or a banded static-HLO
    cost, so this runs on any CPU box — the tier-1 suite runs it, which
    means CPU CI guards TPU-relevant cost invariants on every PR."""
    from text_crdt_rust_tpu.obs.ledger import (
        cpu_cell_names,
        diff_ledger,
        load_ledger,
        validate_ledger,
    )

    # The probe owns the derivations (and the sp cell's virtual-mesh
    # XLA_FLAGS setup, applied at import before the CPU client exists).
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "perf"))
    import cost_ledger_probe as probe

    committed = load_ledger(args.ledger)
    validate_ledger(committed)
    cheap = cpu_cell_names(committed)
    want = args.cells.split(",") if args.cells else cheap
    not_cpu = [c for c in want if c not in cheap]
    if not_cpu:
        log(f"--check-ledger refused: cells {not_cpu} are not cpu "
            f"cells of {args.ledger} (device cells are recorded on "
            f"the chip by perf/cost_ledger_probe.py --device)")
        return 2
    # A committed cpu cell the probe no longer knows IS drift (a cell
    # rename/removal without a re-record) — report it as a named
    # finding, don't crash on the derive call.
    diffs = [f"{c}: committed as a cpu cell but the probe no longer "
             f"derives it (re-record perf/COST_LEDGER.json)"
             for c in want if c not in probe.CPU_CELLS]
    fresh = probe.derive_cells([c for c in want if c in probe.CPU_CELLS])
    ok, cell_diffs = diff_ledger(committed, fresh,
                                 jax_version=jax.__version__)
    diffs.extend(cell_diffs)
    ok = not diffs
    for d in diffs:
        log(f"LEDGER DRIFT: {d}")
    n_metrics = sum(len(c["metrics"]) for c in fresh.values())
    if ok:
        log(f"cost ledger OK: {len(fresh)} cells / {n_metrics} metrics "
            f"re-derived bit-for-logical-bit against {args.ledger}")
    print(json.dumps({"ledger_ok": ok, "ledger": args.ledger,
                      "cells_checked": sorted(fresh),
                      "metrics_checked": n_metrics, "diffs": diffs}))
    return 0 if ok else 1


# ------------------------------------------------------------------ main --


def main() -> None:
    from text_crdt_rust_tpu.config import ENGINE_CHOICES

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="northstar",
                    choices=("northstar", "1", "2", "3", "4", "5", "5r",
                             "kevin", "serve", "serve-lanes", "sp",
                             "all"))
    ap.add_argument("--trace", default="automerge-paper")
    ap.add_argument("--patches", type=int, default=0,
                    help="northstar trace prefix (0 = FULL trace)")
    ap.add_argument("--batch", type=int, default=0,
                    help="identical-doc lanes (0 = per-config default: "
                         "northstar 512 at capacity <= 20992 else 256, "
                         "others 128)")
    ap.add_argument("--engine", choices=ENGINE_CHOICES, default="rle")
    ap.add_argument("--groups", type=int, default=1,
                    help="northstar doc groups (rle engines; docs = "
                         "batch x groups in one launch)")
    ap.add_argument("--kevin-n", type=int, default=5_000_000,
                    help="kevin TPU prepend count (default = the full "
                         "reference workload, benches/yjs.rs:51-62)")
    ap.add_argument("--fuse-w", type=int, default=0,
                    help="fused burst width: kevin's split-batch "
                         "prepare (0 = default 64 full / 8 smoke) and "
                         "northstar's generalized fuse_steps pass "
                         "(0 = default 8); 1 = unfused everywhere")
    ap.add_argument("--merge-rows", action="store_true",
                    help="with a single --config: merge the produced "
                         "rows into --out (replacing that cfg_key's "
                         "prior rows) instead of print-only")
    ap.add_argument("--capacity", type=int, default=0,
                    help="rle engine run-row capacity (0 = default 20992 "
                         "for rle, 32768 for rle-hbm; rounded up to a "
                         "block_k multiple)")
    ap.add_argument("--lanes-block-k", type=int, default=64,
                    help="K (rows per block) for the blocked per-lane "
                         "engines, configs 5/5r")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU backend (logic check; implies "
                         "--interpret --smoke)")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload sizes (CI / CPU logic checks)")
    ap.add_argument("--lax-check", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="with --config all: keep clean rows already in "
                         "--out, re-run only missing/error configs")
    ap.add_argument("--out", default="BENCH_ALL.json")
    ap.add_argument("--check-ledger", action="store_true",
                    help="re-derive the committed cost ledger's cpu "
                         "cells (perf/COST_LEDGER.json) and exit "
                         "nonzero with named per-metric diffs on drift "
                         "— the wall-clock-free perf regression gate")
    ap.add_argument("--ledger", default="perf/COST_LEDGER.json",
                    help="ledger artifact for --check-ledger")
    ap.add_argument("--cells", default=None,
                    help="with --check-ledger: comma-separated cell "
                         "subset (default: every cpu cell)")
    args = ap.parse_args()

    if args.check_ledger:
        # CPU-only by construction (the whole point); never touches the
        # chip.
        jax.config.update("jax_platforms", "cpu")
        raise SystemExit(run_ledger_check(args))

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        args.interpret = True
        args.smoke = True
        args.reps = 1
        dev = jax.devices()[0]
    else:
        dev = require_tpu()
    from text_crdt_rust_tpu.utils.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind}; compile cache "
        f"{enable_compile_cache()}")

    fns = {
        "northstar": cfg_northstar,
        "1": cfg_1_cpu,
        "2": cfg_2,
        "3": cfg_3,
        "4": cfg_4,
        "5": cfg_5,
        "5r": cfg_5_remote,
        "kevin": cfg_kevin,
        "serve": cfg_serve,
        "serve-lanes": cfg_serve_lanes,
        "sp": cfg_sp,
    }
    variant = (f"smoke={args.smoke},engine={args.engine},"
               f"batch={args.batch},groups={args.groups},"
               f"kevin_n={args.kevin_n},patches={args.patches},"
               f"fuse_w={args.fuse_w}")
    if args.config != "all":
        out = fns[args.config](args)
        rows = out if isinstance(out, list) else [out]
        if args.merge_rows:
            merge_config_rows(args.out, args.config, rows, variant,
                              smoke=args.smoke)
        print(json.dumps(rows[0]))
        if len(rows) > 1:
            log(json.dumps(rows[1:]))
        return

    sink = RowSink(args.out, resume=args.resume, variant=variant)
    # Rows are written as each config completes, so a crash keeps the
    # finished ones; the CPU-capable serve/sp/1 configs run last.
    failed = []
    for key in ("northstar", "kevin", "4", "5r", "5", "2", "3",
                "serve", "serve-lanes", "sp", "1"):
        if key in sink.done_keys:
            log(f"=== config {key} === (resumed from {args.out})")
            continue
        log(f"=== config {key} ===")
        try:
            sink.add(key, fns[key](args))
        except Exception as e:  # keep the suite going; record the failure
            log(f"config {key} FAILED: {type(e).__name__}: {e}")
            failed.append(key)
            sink.add(key, {"config": key,
                           "error": f"{type(e).__name__}: {e}"})
    log(f"wrote {len(sink.rows)} rows to {args.out}")
    star = next((r for r in sink.rows
                 if r.get("config", "").startswith("northstar")
                 and "error" not in r), sink.rows[0])
    print(json.dumps(star))
    if failed:
        raise SystemExit(f"bench.py: configs {failed} failed (error rows "
                         f"written to {args.out})")


if __name__ == "__main__":
    main()
