"""Tier-1 smoke for ``perf/fused_trace_probe.py`` (ISSUE 6 CI
satellite): the committed ``perf/fused_traces_r9.json`` is produced by
the probe's full path; this asserts its small-scale path stays green —
a real-trace prefix at event granularity, fused vs unfused, bit-exact
on all four fused-splice surfaces (rle / rle-hbm / blocked lanes /
blocked lanes-mixed) — so a kernel or fuser regression cannot land
while the JSON silently rots.

The smoke calls ``identity_prefix`` IN-PROCESS at the probe's own tight
geometry (a subprocess would re-pay the jax import; the suite's shared
512-row geometry was measured SLOWER here — fatter interpret replays
cost more than warm-cache builds save).  The probe's CLI and JSON
writer are exercised by the ``slow``-tier claims check below.
"""
import importlib.util
import json
import os

import pytest

PROBE = os.path.join("perf", "fused_trace_probe.py")


def _load_probe():
    spec = importlib.util.spec_from_file_location("ftp", PROBE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Slow tier since PR 17 (wall budget: ~23 s of the 870 s gate): the
# fused-splice surfaces keep tier-1 bit-exactness coverage in
# test_rle_fused / test_lanes_blocked; the full claims check below was
# always slow-tier.
@pytest.mark.slow
def test_probe_smoke_path_green():
    row = _load_probe().identity_prefix(
        "automerge-paper", 60, fuse_w=6, chunk=64)
    assert row["oracle_equal"]
    assert set(row["bit_identical"]) == {
        "rle", "rle-hbm", "rle-lanes-blocked", "rle-lanes-mixed-blocked"}
    assert all(row["bit_identical"].values())
    assert row["steps_fused"] < row["steps_unfused"]


@pytest.mark.slow
def test_committed_r9_json_claims_hold():
    """The committed probe JSON's headline claims re-checked against
    the CURRENT compiler+fuser (host arithmetic only — no replay): the
    full-trace step cut is reproducible and >= the acceptance floor —
    ``slow`` because it recompiles the full automerge trace (the tier-1
    budget keeps only the in-process smoke above)."""
    with open(os.path.join("perf", "fused_traces_r9.json")) as f:
        committed = json.load(f)
    assert committed["acceptance"]["pass"]
    mod = _load_probe()
    want = {c["trace"]: c for c in committed["full_trace_step_cut"]}
    cut = mod.full_trace_cut("automerge-paper",
                             committed["workload"]["fuse_w"])
    assert cut["steps_unfused"] == want["automerge-paper"]["steps_unfused"]
    assert cut["steps_fused"] == want["automerge-paper"]["steps_fused"]
    assert cut["step_reduction_x"] >= committed["acceptance"]["floor_x"]
