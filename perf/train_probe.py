"""Tick-train probe (ISSUE 20 acceptance): T ticks as one device
``lax.scan`` program vs the serial one-dispatch-per-tick loop, at the
200-doc faulted acceptance shape.

Three arms of the SAME seeded loadgen (the ``device_prefill_probe``
pattern): train depth {1, 2, 4}, all at pipeline depth 2 with
device-resident prefill.  Every arm's logical stream is sha256-hashed
and ALL THREE must be identical — train length is a wall-clock knob
only.  Per arm the probe records:

- **dispatch economy** (the ledger-gated counters): device dispatches,
  dispatches per tick, and ``dispatch_cut_x`` — the serial-equivalent
  dispatch count over the actual one.  The committed depth-4 cut must
  be >= 3x (theoretical ceiling at depth 4 is 8/2 = 4x: T step
  dispatches + T scatter dispatches collapse to 1 train scan + 1
  concatenated scatter; partial flushes at lane residency boundaries
  eat the rest).
- **loop wall** (min of ``reps``): no train depth may regress depth 1
  by > 5%.  On the CPU tier-1 box each dispatch is a cheap Python
  call, so the honest readout is parity-within-noise; a chip run
  (``--device``) is where T-for-one dispatch amortization would pay.
- **compile economy**: distinct (T-bucket, S-bucket) train programs
  compiled — the power-of-two pad series must keep this bounded (the
  compile set is ADDITIVE: train programs + scatter programs, because
  the concatenated scatter stays a separate dispatch).

Writes ``perf/train_r17.json``.

Run: python perf/train_probe.py [--smoke] [--reps N] [--out P]
"""
import argparse
import hashlib
import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402

if "--device" not in sys.argv:
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # in-process import after backend init (the tier-1 smoke)

from text_crdt_rust_tpu.config import ServeConfig  # noqa: E402
from text_crdt_rust_tpu.serve.loadgen import ServeLoadGen  # noqa: E402

WALL_REGRESSION_PCT = 5.0
DISPATCH_CUT_FLOOR_X = 3.0
TRAIN_DEPTHS = (4, 2, 1)


def run_one(smoke: bool, *, train_ticks: int, seed: int = 7):
    """One seeded loadgen run; returns (report, loop_wall_s, sha256)."""
    docs, ticks, events = (24, 12, 16) if smoke else (200, 60, 48)
    cfg = ServeConfig(engine="flat", num_shards=4, lanes_per_shard=16,
                      pipeline_ticks=2, train_ticks=train_ticks,
                      flow_sample_mod=16, trace_keep=True)
    gen = ServeLoadGen(docs=docs, agents_per_doc=3, ticks=ticks,
                       events_per_tick=events, zipf_alpha=1.1,
                       fault_rate=0.10, local_prob=0.25, seed=seed,
                       cfg=cfg)
    t0 = time.perf_counter()
    rep = gen.run()
    wall = time.perf_counter() - t0
    assert rep["converged"], rep["mismatches"][:4]
    sha = hashlib.sha256(
        gen.server.tracer.logical_bytes()).hexdigest()
    return rep, wall, sha


def _arm_row(rep: dict) -> dict:
    tr = rep["train"]
    return {
        "train_ticks": tr["ticks"],
        "loop_wall_s": rep["device_ticks_wall_s"],
        "device_dispatches": tr["device_dispatches"],
        "dispatches_per_tick": tr["dispatches_per_tick"],
        "dispatch_cut_x": tr["dispatch_cut_x"],
        "train_len": tr["train_len"],
        "train_compiles": tr["train_compiles"],
        "device_steps": rep["server"].get("device_steps", 0),
        "device_compiles": rep["server"].get("device_compiles", 0),
        "evictions": rep["server"].get("evictions", 0),
        "flow_audit_ok": rep["flow"]["audit_ok"],
        "flow_age_p50": rep["flow"]["ages_ticks"]["p50"],
    }


def _warm_compiles(smoke: bool) -> None:
    """Warm every jit cache untimed BEFORE any timed arm: the per-tick
    step/scatter programs via one smoke run per depth, then EVERY
    (T-bucket, S-bucket) train program a full-scale run can hit — a
    partial flush at an eviction boundary can dispatch any (T, S) pair,
    and one mid-arm train compile (~0.5 s x up to 12 distinct programs)
    would bill compiler order as dispatch cost (the first cut of this
    probe measured exactly that as a fake 12% wall regression)."""
    import numpy as np

    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import flat as F
    from text_crdt_rust_tpu.serve.batcher import FlatLaneBackend

    for t in TRAIN_DEPTHS:
        run_one(True, train_ticks=t)
    cfg = ServeConfig()
    backend = FlatLaneBackend(lanes=cfg.lanes_per_shard,
                              capacity=cfg.lane_capacity,
                              order_capacity=cfg.order_capacity,
                              lmax=cfg.lmax)
    lanes = cfg.lanes_per_shard
    for s_bkt in cfg.step_buckets:
        tick = B.stack_ops(
            [B.pad_ops(B.empty_ops(cfg.lmax), s_bkt)] * lanes)
        for t_bkt in (1, 2, 4):
            train = B.stack_ticks([tick] * t_bkt)
            F.apply_train(backend.docs, train)
    bucket_cap = cfg.step_buckets[-1] * cfg.lmax
    L = B.PREFILL_BUCKET_BASE
    while L <= bucket_cap:
        pad = np.full((lanes, L), B.PREFILL_PAD, np.uint32)
        zero = np.zeros_like(pad)
        delta = B.PrefillDelta(pad, zero, zero, pad, zero, pad, zero,
                               bucket=L)
        F.apply_prefill_delta(backend.docs, delta)
        L *= 4


def run_matrix(smoke: bool = False, reps: int = 2) -> dict:
    _warm_compiles(smoke)
    arms = {}
    hashes = {}
    walls = {f"train{t}": [] for t in TRAIN_DEPTHS}
    best = {}
    # Interleave the reps (arm order inside each rep round) so shared-
    # box drift lands evenly across arms; min-of-reps per arm.
    for _ in range(reps):
        for t in TRAIN_DEPTHS:
            key = f"train{t}"
            rep, wall, h = run_one(smoke, train_ticks=t)
            assert hashes.setdefault(key, h) == h, \
                "same-seed arm reruns diverged"
            walls[key].append(rep["device_ticks_wall_s"])
            if (key not in best or rep["device_ticks_wall_s"]
                    < best[key]["device_ticks_wall_s"]):
                best[key] = rep
    for key, rep in best.items():
        arms[key] = _arm_row(rep)
        arms[key]["loop_walls_s"] = walls[key]

    identical = len(set(hashes.values())) == 1
    t4, t2, t1 = arms["train4"], arms["train2"], arms["train1"]
    wall_delta_pct = {
        "train4": round((t4["loop_wall_s"] - t1["loop_wall_s"])
                        / t1["loop_wall_s"] * 100.0, 2),
        "train2": round((t2["loop_wall_s"] - t1["loop_wall_s"])
                        / t1["loop_wall_s"] * 100.0, 2),
    }
    logical_counters_identical = all(
        a["device_steps"] == t1["device_steps"]
        and a["device_compiles"] == t1["device_compiles"]
        and a["evictions"] == t1["evictions"]
        and a["flow_age_p50"] == t1["flow_age_p50"]
        and a["flow_audit_ok"]
        for a in arms.values())

    out = {
        "probe": "train",
        "smoke": smoke,
        "workload": {
            "docs": 24 if smoke else 200, "seed": 7, "engine": "flat",
            "fault_rate": 0.10, "reps_per_arm": reps,
            "basis": "min loop wall (device_ticks_wall_s) per arm; "
                     "logical metrics from the min-wall rep",
        },
        "arms": arms,
        "stream_sha256": hashes,
        "acceptance": {
            "dispatch_cut_floor_x": DISPATCH_CUT_FLOOR_X,
            "wall_regression_bar_pct": WALL_REGRESSION_PCT,
            "streams_sha256_identical": identical,
            "logical_counters_identical": logical_counters_identical,
            "dispatch_cut_x": {"train4": t4["dispatch_cut_x"],
                               "train2": t2["dispatch_cut_x"],
                               "train1": t1["dispatch_cut_x"]},
            "wall_delta_pct": wall_delta_pct,
            # Smoke walls are sub-second shared-box noise: the wall bar
            # gates only the full-scale (committed) run, like the
            # device-prefill probe's smoke tier.  Smoke runs are also
            # too short to amortize partial flushes, so the cut floor
            # relaxes to "deeper trains strictly cut dispatches".
            "pass": bool(
                identical and logical_counters_identical
                and t1["dispatch_cut_x"] == 1.0
                and t4["dispatch_cut_x"] > t2["dispatch_cut_x"] > 1.0
                and (smoke
                     or t4["dispatch_cut_x"] >= DISPATCH_CUT_FLOOR_X)
                and (smoke or max(wall_delta_pct.values())
                     <= WALL_REGRESSION_PCT)),
        },
        "note": "CPU run (tier-1 harness): a dispatch here is a cheap "
                "Python-to-XLA call, so the wall gate is parity-within-"
                "noise (<=5%); the dispatch cut is the structural win "
                "and a chip run is where T-for-one launch "
                "amortization would show up as wall. "
                "Logical metrics are seed-deterministic and platform-"
                "independent; depth-4 cut < 4x ceiling because lane "
                "residency boundaries (evict, upload, rank-table "
                "growth on an active lane) force partial flushes.",
    }
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--device", action="store_true",
                    help="run on the default jax backend instead of "
                         "forcing CPU (on the chip; write to a separate "
                         "--out so the committed CPU record stays the "
                         "tier-1 reference)")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--out", default="perf/train_r17.json")
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
