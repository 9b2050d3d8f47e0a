"""Pipelined-tick probe (ISSUE 12 acceptance): host/device overlap,
serial-vs-pipelined equivalence, and the two serve-loop tuning sweeps
(Nagle emission window, typing lmax) at the 200-doc faulted acceptance
shape.

Four sections of the SAME seeded loadgen (the §14/§16 probe pattern):

- ``pipeline``   — serial (``pipeline_ticks=1``) vs double-buffered
  (``2``) arms, timed (min of ``reps`` loop walls).  The pipelined arm
  must show ``overlap_frac > 0`` (device-sync demand hidden under host
  work) WITHOUT regressing the serial loop wall > 5%; two untimed
  ``trace_keep`` runs additionally pin that the two modes emit
  **byte-identical logical streams** (flow events included) and
  identical flow audits/op-age distributions — pipelining moves wall
  time only.
- ``nagle``      — the §16 latency lever: sweep the columnar-wire
  emission window (``nagle_txns``/``nagle_rounds``) at full flow
  sampling and read clean-remote op-age (emission-to-frame batching
  dominates it) against the bytes/op cost of smaller batches.  The
  shipped ServeConfig default must cut clean-remote p50 from the old
  64-txn window's ~12 ticks to <= 6.
- ``lmax``       — the typing-workload step-economy lever (the PR-6
  fusion cap): sweep ``ServeConfig.lmax`` over 8/16/32 on ``--workload
  typing`` and record device steps, ops/step and loop wall; the
  shipped default is the sweep winner.
- ``defaults``   — one run at the exact shipped ServeConfig, asserting
  the acceptance numbers hold at the defaults users get.

Logical metrics (ages, steps, bytes) are seed-deterministic; wall
numbers carry shared-box noise and gate only the 5% regression bar.
Writes ``perf/pipeline_r14.json``.

Run: python perf/pipeline_probe.py [--smoke] [--reps N] [--out PATH]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except RuntimeError:
    pass  # in-process import after backend init (the tier-1 smoke)

from text_crdt_rust_tpu.config import ServeConfig  # noqa: E402
from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen  # noqa: E402

WALL_REGRESSION_PCT = 5.0
CLEAN_P50_FLOOR_TICKS = 6
# (nagle_txns, nagle_rounds) arms: the first approximates the pre-ISSUE-12
# behavior (64 txns / 6 resync windows x resync_every=4 ticks), the rest
# walk the window down to near-per-event emission.
NAGLE_ARMS = ((64, 24), (64, 6), (32, 8), (16, 4), (16, 2), (8, 2),
              (4, 1))
NAGLE_ARMS_SMOKE = ((64, 24), (16, 2), (4, 1))
LMAX_ARMS = (8, 16, 32)


def run_one(smoke: bool, *, pipeline_ticks=None, nagle=None, lmax=None,
            workload="scatter", flow_mod=1, keep_trace=False, seed=7):
    """One seeded loadgen run; returns (report, wall_s, logical_trace)."""
    docs, ticks, events = (24, 12, 16) if smoke else (200, 60, 48)
    kw = {}
    if pipeline_ticks is not None:
        kw["pipeline_ticks"] = pipeline_ticks
    if nagle is not None:
        kw["nagle_txns"], kw["nagle_rounds"] = nagle
    if lmax is not None:
        kw["lmax"] = lmax
    cfg = ServeConfig(engine="flat", num_shards=2, lanes_per_shard=16,
                      flow_sample_mod=flow_mod, trace_keep=keep_trace,
                      **kw)
    gen = ServeLoadGen(docs=docs, agents_per_doc=3, ticks=ticks,
                       events_per_tick=events, zipf_alpha=1.1,
                       fault_rate=0.10, local_prob=0.25, seed=seed,
                       cfg=cfg, workload=workload)
    t0 = time.perf_counter()
    rep = gen.run()
    wall = time.perf_counter() - t0
    assert rep["converged"], rep["mismatches"][:4]
    trace = gen.server.tracer.logical_bytes() if keep_trace else None
    return rep, wall, trace


def _age_row(rep: dict) -> dict:
    f = rep["flow"]
    w = rep["wire"]
    return {
        "audit_ok": f["audit_ok"],
        "age_p50": f["ages_ticks"]["p50"],
        "age_p99": f["ages_ticks"]["p99"],
        "clean_p50": f["by_class"]["clean"]["p50"],
        "clean_p99": f["by_class"]["clean"]["p99"],
        "redelivered_p50": f["by_class"]["redelivered"]["p50"],
        "bytes_per_op": w["bytes_per_op"],
        "push_bytes": w["push_bytes"],
        "pull_bytes": w["pull_bytes"],
    }


def run_matrix(smoke: bool = False, reps: int = 2) -> dict:
    # -- 1. pipeline: serial vs double-buffered, timed -------------------
    pipeline = {}
    loops = {}
    for name, pt in (("serial", 1), ("pipelined", 2)):
        best = None
        for _ in range(reps):
            rep, wall, _ = run_one(smoke, pipeline_ticks=pt,
                                   flow_mod=16)
            if (best is None or rep["device_ticks_wall_s"]
                    < best["device_ticks_wall_s"]):
                best = rep
        # Report the WHOLE min-wall rep, so loop_wall_s and its
        # overlap/stall/tick metrics all come from one execution (a
        # min-of-walls paired with another rep's overlap would mix
        # runs in the committed artifact).
        loops[name] = best["device_ticks_wall_s"]
        pipeline[name] = {
            "pipeline_ticks": best["pipeline"]["ticks"],
            "loop_wall_s": round(loops[name], 3),
            "overlap_frac": best["pipeline"]["overlap_frac"],
            "stall_ms_total": best["pipeline"]["stall_ms_total"],
            "tick_p50_ms": best["tick_ms"]["p50"],
            "tick_p99_ms": best["tick_ms"]["p99"],
        }
    wall_delta_pct = round(
        (loops["pipelined"] - loops["serial"]) / loops["serial"] * 100.0,
        2)

    # Byte-identity across modes (untimed, full sampling + retention):
    # the logical stream INCLUDING flow spans must not know whether the
    # barrier was deferred.
    rep_s, _, tr_s = run_one(smoke, pipeline_ticks=1, keep_trace=True)
    rep_p, _, tr_p = run_one(smoke, pipeline_ticks=2, keep_trace=True)
    identical = tr_s == tr_p
    flow_identical = (rep_s["flow"]["ages_ticks"] ==
                      rep_p["flow"]["ages_ticks"]
                      and rep_s["flow"]["spans"] == rep_p["flow"]["spans"]
                      and rep_s["flow"]["audit_ok"]
                      and rep_p["flow"]["audit_ok"])

    # -- 2. nagle sweep (logical metrics are seed-deterministic) ---------
    nagle = {}
    for arm in (NAGLE_ARMS_SMOKE if smoke else NAGLE_ARMS):
        rep, wall, _ = run_one(smoke, nagle=arm)
        nagle[f"{arm[0]}/{arm[1]}"] = {
            **_age_row(rep), "loop_wall_s": rep["device_ticks_wall_s"]}

    # -- 3. lmax sweep on the typing workload ----------------------------
    lmax = {}
    for lm in LMAX_ARMS:
        rep, wall, _ = run_one(smoke, lmax=lm, workload="typing",
                               flow_mod=16)
        lmax[str(lm)] = {
            "steps_total": rep["tick_ms"]["steps_total"],
            "steps_prefuse": rep["tick_ms"]["steps_prefuse"],
            "ops_per_step": rep["tick_ms"]["ops_per_step"],
            "device_steps_padded": rep["server"].get("device_steps", 0),
            "bytes_per_op": rep["wire"]["bytes_per_op"],
            "loop_wall_s": rep["device_ticks_wall_s"],
        }

    # -- 4. the shipped defaults -----------------------------------------
    d = ServeConfig()
    rep_def, _, _ = run_one(smoke)
    defaults = {
        "pipeline_ticks": d.pipeline_ticks,
        "nagle_txns": d.nagle_txns,
        "nagle_rounds": d.nagle_rounds,
        "lmax": d.lmax,
        **_age_row(rep_def),
        "overlap_frac": rep_def["pipeline"]["overlap_frac"],
    }

    baseline_key = "64/24"
    out = {
        "probe": "pipelined_tick",
        "smoke": smoke,
        "workload": {
            "docs": rep_def["docs"], "seed": 7, "engine": "flat",
            "fault_rate": 0.10, "reps_per_timed_arm": reps,
            "basis": "min loop wall (device_ticks_wall_s) per arm",
        },
        "pipeline": {
            **pipeline,
            "wall_delta_pct": wall_delta_pct,
            "logical_streams_byte_identical": identical,
            "flow_reports_identical": flow_identical,
        },
        "nagle_sweep": nagle,
        "lmax_sweep": lmax,
        "defaults": defaults,
        "acceptance": {
            "wall_regression_bar_pct": WALL_REGRESSION_PCT,
            "clean_p50_floor_ticks": CLEAN_P50_FLOOR_TICKS,
            "clean_p50_before": nagle.get(baseline_key, {}).get(
                "clean_p50"),
            "clean_p50_shipped": defaults["clean_p50"],
            "pass": bool(
                identical and flow_identical
                and pipeline["pipelined"]["overlap_frac"] > 0.0
                and wall_delta_pct <= WALL_REGRESSION_PCT
                and defaults["audit_ok"]
                and defaults["clean_p50"] <= CLEAN_P50_FLOOR_TICKS),
        },
        "note": "CPU run (tier-1 harness): XLA CPU saturates the cores, "
                "so the overlap window mostly hides dispatch/sync "
                "latency rather than buying wall — the bar here is "
                "overlap>0 at <=5% wall cost; a chip run measures the "
                "real hidden device time.  Logical metrics (ages, "
                "steps, bytes) are seed-deterministic and "
                "platform-independent.",
    }
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--out", default="perf/pipeline_r14.json")
    a = ap.parse_args()
    out = run_matrix(smoke=a.smoke, reps=a.reps)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out, indent=1))
    if not out["acceptance"]["pass"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
