"""Device-resident prefill probe (ISSUE 14 acceptance): the delta-
scatter serve tick vs the full-log host round trip, at the 200-doc
faulted acceptance shape.

Four arms of the SAME seeded loadgen (the ``pipeline_probe`` pattern):
{host-prefill, delta-prefill} x pipeline depth {1, 2}.  Every arm's
logical stream is sha256-hashed and ALL FOUR must be identical — the
prefill mode and the pipeline depth may move bytes and wall only.  Per
arm the probe records:

- **prefill bytes moved per tick**: the delta path ships the padded
  scatter tensors (7 u32 columns x bucket length x lanes); the host
  path materializes AND re-uploads the four full [B, OCAP] logs
  (2 x 4 x OCAP x B x 4 bytes).  The committed cut must be >= 20x
  (the acceptance floor; the §19 cost model predicts ~40x at this
  shape).
- **loop wall** (min of ``reps``): the delta arm must not regress the
  host arm > 5% at either depth.  On the CPU tier-1 box the prefill
  round trip is a small slice of the tick, so the honest readout is
  parity-within-noise; a chip run is where the removed dispatch-edge
  sync would pay.
- **scatter economy**: un-padded scatter length, compiled
  scatter-bucket count (steady state must stay bounded), and the
  flow/ledger counters that must not move across arms.

Writes ``perf/device_prefill_r16.json``.

Run: python perf/device_prefill_probe.py [--smoke] [--reps N] [--out P]
"""
import argparse
import hashlib
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
BYTES_CUT_FLOOR_X = 20.0
ARMS = tuple((dp, pt) for dp in ("delta", "host") for pt in (2, 1))


def run_one(smoke: bool, *, device_prefill: bool, pipeline_ticks: int,
            seed: int = 7):
    """One seeded loadgen run; returns (report, loop_wall_s, sha256)."""
    docs, ticks, events = (24, 12, 16) if smoke else (200, 60, 48)
    cfg = ServeConfig(engine="flat", num_shards=2, lanes_per_shard=16,
                      device_prefill=device_prefill,
                      pipeline_ticks=pipeline_ticks,
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
    pf = rep["prefill"]
    return {
        "device_prefill": pf["device_prefill"],
        "pipeline_ticks": rep["pipeline"]["ticks"],
        "overlap_frac": rep["pipeline"]["overlap_frac"],
        "loop_wall_s": rep["device_ticks_wall_s"],
        "prefill_bytes_per_tick": pf["bytes_per_tick"],
        "prefill_bytes_full_per_tick": pf["bytes_full_per_tick"],
        "prefill_bytes_cut_x": pf["bytes_cut_x"],
        "prefill_scatter_len": pf["scatter_len"],
        "prefill_scatter_compiles": pf["scatter_compiles"],
        "device_steps": rep["server"].get("device_steps", 0),
        "device_compiles": rep["server"].get("device_compiles", 0),
        "evictions": rep["server"].get("evictions", 0),
        "flow_audit_ok": rep["flow"]["audit_ok"],
        "flow_age_p50": rep["flow"]["ages_ticks"]["p50"],
    }


def _warm_compiles(smoke: bool) -> None:
    """Warm every jit cache untimed BEFORE any timed arm: the step
    programs via one smoke run per mode, and the scatter programs for
    EVERY bucket a full-scale tick can hit (the smoke run's small
    scatters never reach the big buckets, and a mid-arm ~0.7 s scatter
    compile would bill compiler order as prefill cost — the first cut
    of this probe measured exactly that)."""
    import numpy as np

    from text_crdt_rust_tpu.ops import batch as B
    from text_crdt_rust_tpu.ops import flat as F
    from text_crdt_rust_tpu.serve.batcher import FlatLaneBackend

    for dp in (True, False):
        run_one(True, device_prefill=dp, pipeline_ticks=2)
    cfg = ServeConfig()
    backend = FlatLaneBackend(lanes=cfg.lanes_per_shard,
                              capacity=cfg.lane_capacity,
                              order_capacity=cfg.order_capacity,
                              lmax=cfg.lmax)
    bucket_cap = cfg.step_buckets[-1] * cfg.lmax
    L = B.PREFILL_BUCKET_BASE
    while L <= bucket_cap:
        pad = np.full((cfg.lanes_per_shard, L), B.PREFILL_PAD,
                      np.uint32)
        zero = np.zeros_like(pad)
        delta = B.PrefillDelta(pad, zero, zero, pad, zero, pad, zero,
                               bucket=L)
        F.apply_prefill_delta(backend.docs, delta)
        L *= 4


def run_matrix(smoke: bool = False, reps: int = 2) -> dict:
    _warm_compiles(smoke)
    arms = {}
    hashes = {}
    walls = {f"{dp}/depth{pt}": [] for dp, pt in ARMS}
    best = {}
    # Interleave the reps (arm order inside each rep round) so shared-
    # box drift lands evenly across arms; min-of-reps per arm.
    for _ in range(reps):
        for dp, pt in ARMS:
            key = f"{dp}/depth{pt}"
            rep, wall, h = run_one(smoke, device_prefill=dp == "delta",
                                   pipeline_ticks=pt)
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
    delta2, host2 = arms["delta/depth2"], arms["host/depth2"]
    delta1, host1 = arms["delta/depth1"], arms["host/depth1"]
    wall_delta_pct = {
        "depth2": round((delta2["loop_wall_s"] - host2["loop_wall_s"])
                        / host2["loop_wall_s"] * 100.0, 2),
        "depth1": round((delta1["loop_wall_s"] - host1["loop_wall_s"])
                        / host1["loop_wall_s"] * 100.0, 2),
    }
    logical_counters_identical = all(
        a["device_steps"] == delta2["device_steps"]
        and a["device_compiles"] == delta2["device_compiles"]
        and a["evictions"] == delta2["evictions"]
        and a["flow_age_p50"] == delta2["flow_age_p50"]
        and a["flow_audit_ok"]
        for a in arms.values())

    out = {
        "probe": "device_prefill",
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
            "bytes_cut_floor_x": BYTES_CUT_FLOOR_X,
            "wall_regression_bar_pct": WALL_REGRESSION_PCT,
            "streams_sha256_identical": identical,
            "logical_counters_identical": logical_counters_identical,
            "prefill_bytes_cut_x": delta2["prefill_bytes_cut_x"],
            "wall_delta_pct": wall_delta_pct,
            # Smoke walls are sub-second shared-box noise: the wall bar
            # gates only the full-scale (committed) run, like the
            # pipeline probe's smoke tier.
            "pass": bool(
                identical and logical_counters_identical
                and delta2["prefill_bytes_cut_x"] >= BYTES_CUT_FLOOR_X
                and delta1["prefill_bytes_cut_x"] >= BYTES_CUT_FLOOR_X
                and (smoke or max(wall_delta_pct.values())
                     <= WALL_REGRESSION_PCT)
                and delta2["overlap_frac"] > 0.0),
        },
        "note": "CPU run (tier-1 harness): the full-log round trip is "
                "host-memory traffic here, so the wall gate is "
                "parity-within-noise (<=5%); the byte cut and the "
                "removed dispatch-edge device read are the structural "
                "wins, and a chip run is where the hidden-sync "
                "removal would show up as overlap. "
                "Logical metrics are seed-deterministic and "
                "platform-independent.",
    }
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--out", default="perf/device_prefill_r16.json")
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
