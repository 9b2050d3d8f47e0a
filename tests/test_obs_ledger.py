"""Cost ledger + regression gate (ISSUE 10): the committed
``perf/COST_LEDGER.json`` validates and covers the acceptance floor,
``bench.py --check-ledger`` re-derives every cpu cell deterministically,
and an injected drift fails the gate LOUD with the metric named.

The end-to-end gate run uses a mutated copy of the committed ledger and
asserts the diff list contains EXACTLY the injected metric — which
simultaneously proves (a) every other committed metric re-derived
bit-for-logical-bit (the clean gate would pass), and (b) the gate fails
with a precise name on drift (the drift-injection acceptance), for the
price of one subprocess."""
import json
import os
import subprocess
import sys

import pytest

from text_crdt_rust_tpu.obs.ledger import (
    LEDGER_SCHEMA_VERSION,
    METRIC_FAMILIES,
    cpu_cell_names,
    diff_cell,
    diff_ledger,
    families_covered,
    load_ledger,
    metric,
    validate_ledger,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEDGER = os.path.join(REPO, "perf", "COST_LEDGER.json")


# ------------------------------------------------- committed artifact ----


def test_committed_ledger_validates_and_covers_acceptance_floor():
    led = load_ledger(LEDGER)
    validate_ledger(led)  # raises naming violations
    assert led["schema_version"] == LEDGER_SCHEMA_VERSION
    # ISSUE 10 acceptance: >= 6 metric families across at least the
    # serve, serve-lanes, fused-trace and sp cells.
    assert {"serve", "serve-lanes", "fused-trace", "sp"} <= set(
        led["cells"])
    fams = families_covered(led)
    assert len(fams) >= 6, fams
    assert fams <= set(METRIC_FAMILIES)
    # The cpu cells are the wall-clock-free gate's surface.
    assert set(cpu_cell_names(led)) >= {"serve", "serve-lanes",
                                        "fused-trace", "sp"}
    # Headline invariants the ledger now pins: the sp ICI cost model
    # (compiled HLO, so pinned with the jax it was recorded under) and
    # the blocked-lanes touched-row economy.
    assert (led["recorded"]["jax"], led["cells"]["sp"]["metrics"][
        "collectives_per_step"]["v"]) == ("0.9.0", 52)
    assert led["cells"]["serve-lanes"]["metrics"][
        "touched_rows_ratio"]["v"] >= 5


def test_committed_ledger_has_no_wall_metrics_in_cpu_cells():
    """The ledger is a LOGICAL cost contract: wall-clock belongs only
    to device cells (silicon re-record)."""
    led = load_ledger(LEDGER)
    for name in cpu_cell_names(led):
        for mname, m in led["cells"][name]["metrics"].items():
            assert m["family"] != "wall", f"{name}.{mname}"


# ------------------------------------------------------- diff engine ----


def _cell(**metrics):
    return {"kind": "cpu", "workload": {"pin": 1}, "metrics": metrics}


def test_exact_metric_drift_is_named():
    a = _cell(steps=metric(10, "steps"))
    b = _cell(steps=metric(11, "steps"))
    diffs = diff_cell("c", a, b)
    assert len(diffs) == 1
    assert "c.steps" in diffs[0] and "11 != committed 10" in diffs[0]


def test_banded_metric_allows_tolerance_and_catches_escape():
    a = _cell(flops=metric(1000.0, "hlo", tol=0.5))
    assert diff_cell("c", a, _cell(flops=metric(1400.0, "hlo",
                                                tol=0.5))) == []
    diffs = diff_cell("c", a, _cell(flops=metric(1501.0, "hlo",
                                                 tol=0.5)))
    assert len(diffs) == 1 and "outside 1000" in diffs[0]


def test_missing_and_extra_metrics_are_both_drift():
    a = _cell(steps=metric(10, "steps"), gone=metric(1, "steps"))
    b = _cell(steps=metric(10, "steps"), new=metric(2, "steps"))
    diffs = diff_cell("c", a, b)
    assert any("c.gone" in d and "no longer derives" in d for d in diffs)
    assert any("c.new" in d and "never recorded" in d for d in diffs)


def test_diff_ledger_judges_only_derived_cells():
    led = {"cells": {"a": _cell(x=metric(1, "steps")),
                     "dev": {"kind": "device", "workload": {},
                             "metrics": {"w": metric(9, "wall",
                                                     tol=1.0)}}}}
    ok, diffs = diff_ledger(led, {"a": _cell(x=metric(1, "steps"))})
    assert ok and not diffs  # the device cell is not judged
    ok, diffs = diff_ledger(led, {"b": _cell(x=metric(1, "steps"))})
    assert not ok and "committed ledger does not carry" in diffs[0]


def test_validate_ledger_refuses_drifted_schema():
    with pytest.raises(ValueError, match="schema_version"):
        validate_ledger({"schema_version": LEDGER_SCHEMA_VERSION + 1,
                         "cells": {"c": _cell(x=metric(1, "steps"))}})
    with pytest.raises(ValueError, match="unknown family"):
        validate_ledger({"schema_version": LEDGER_SCHEMA_VERSION,
                         "cells": {"c": _cell(
                             x={"v": 1, "family": "nonsense"})}})
    with pytest.raises(ValueError, match="no cells"):
        validate_ledger({"schema_version": LEDGER_SCHEMA_VERSION})


# ------------------------------------------- the gate, end to end -------


def test_check_ledger_gate_rederives_cells_and_fails_loud(tmp_path):
    """ONE subprocess proves both acceptance bars: every cpu-cell
    metric except the injected one re-derives EXACTLY (so the clean
    gate passes), and the injected counter drift fails the gate with
    the metric named (so the gate fails loud)."""
    led = load_ledger(LEDGER)
    led["cells"]["serve"]["metrics"]["steps_total"]["v"] += 1
    mutated = str(tmp_path / "mutated_ledger.json")
    with open(mutated, "w") as f:
        json.dump(led, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--check-ledger",
         "--ledger", mutated],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert r.returncode == 1, (r.stdout, r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ledger_ok"] is False
    assert sorted(out["cells_checked"]) == sorted(cpu_cell_names(led))
    # Exactly the injected metric drifted — everything else matched.
    assert len(out["diffs"]) == 1
    assert "serve.steps_total" in out["diffs"][0]
    assert "LEDGER DRIFT: serve.steps_total" in r.stderr


def test_check_ledger_refuses_device_cells(tmp_path):
    """Asking the CPU gate for a device cell is a usage error (exit 2),
    not a silent skip — device cells wait for the silicon re-record."""
    import argparse

    import bench as bench_mod

    led = load_ledger(LEDGER)
    led["cells"]["fake-dev"] = {"kind": "device", "workload": {"p": 1},
                                "metrics": {"w": metric(1, "wall",
                                                        tol=1.0)}}
    mutated = str(tmp_path / "with_device_cell.json")
    with open(mutated, "w") as f:
        json.dump(led, f)
    args = argparse.Namespace(ledger=mutated, cells="fake-dev")
    # Refusal happens before any derivation, so this is in-process
    # cheap (no jax work).
    assert bench_mod.run_ledger_check(args) == 2


def test_check_ledger_names_a_jax_version_change():
    """Compiled-HLO metrics are the compiler's: under another jax the
    gate asks for a re-record instead of reporting drift, and still
    compares the logical counters exactly."""
    led = load_ledger(LEDGER)
    fresh = json.loads(json.dumps(led["cells"]))
    fresh["sp"]["metrics"]["collectives_per_step"]["v"] += 72
    ok, diffs = diff_ledger(led, fresh, jax_version=led["recorded"]["jax"])
    assert not ok and any("sp.collectives_per_step" in d for d in diffs)
    ok, diffs = diff_ledger(led, fresh, jax_version="0.0.1")
    assert not ok
    assert [d for d in diffs if d.startswith("sp:")] == [
        f"sp: hlo metrics recorded under jax {led['recorded']['jax']}, "
        f"installed jax 0.0.1 — re-record (python "
        f"perf/cost_ledger_probe.py --cells sp)"]
    assert not any("collectives_per_step" in d for d in diffs)
    fresh["sp"]["metrics"]["steps"]["v"] += 1  # logical: still exact
    _, diffs = diff_ledger(led, fresh, jax_version="0.0.1")
    assert any("sp.steps" in d for d in diffs)
