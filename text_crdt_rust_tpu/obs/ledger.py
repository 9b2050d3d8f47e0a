"""Deterministic cost ledger (ISSUE 10 tentpole, part 1).

Every device-side perf claim in this repo is CPU-proven and
silicon-pending, and until now the evidence lived in one-shot
``perf/*_r*.json`` probe files nothing re-checks: a refactor could
silently regress touched rows, fused-step counts, wire bytes/op or
steady-state recompiles and tier-1 would stay green.  The ledger turns
those numbers into a *committed, diffable cost contract*:

- the same logical-first discipline that makes two same-seed loadgen
  runs emit byte-identical traces (PERF.md §14) makes every logical
  cost metric — device steps, fused rows, touched rows/step, wire and
  checkpoint bytes, admission/codec rejects, compile counts — EXACTLY
  reproducible on CPU, so a perf regression gate needs no wall clock
  and no TPU;
- static compiled-HLO costs (collectives/step, flops, bytes accessed
  via ``jit(...).lower(...).compile().cost_analysis()``) are
  reproducible up to compiler version, so they carry a tolerance band
  instead of an exact pin.

``perf/cost_ledger_probe.py`` derives the cells at small pinned
deterministic shapes and commits them as ``perf/COST_LEDGER.json``;
``bench.py --check-ledger`` re-derives every CPU cell and fails with a
named per-metric diff on drift (a tier-1 test runs the gate, so CPU CI
guards TPU-relevant cost invariants on every PR).

Ledger shape::

    {"schema_version": 1,
     "recorded": {...provenance note...},
     "cells": {
       "<cell>": {
         "kind": "cpu" | "device",      # the gate re-derives cpu cells
         "workload": {...pinned shape description...},
         "metrics": {
           "<metric>": {"v": <number>, "family": "<family>",
                        "tol": <relative band, 0.0 = exact>}}}}}

Wall-clock data NEVER enters a cpu cell: the ledger is a logical cost
contract, and wall histograms belong to the ``device`` cells that
``perf/cost_ledger_probe.py --device`` appends on the chip.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

LEDGER_SCHEMA_VERSION = 2  # v2 (ISSUE 16): "recovery" metric family
#                            (journal/replay durability counters)

#: Default committed-artifact location (repo-root relative).
LEDGER_PATH = "perf/COST_LEDGER.json"

#: Known metric families — every metric must claim one, so the
#: committed artifact stays groupable and the coverage floor
#: (>= 6 families, ISSUE 10 acceptance) is checkable.
METRIC_FAMILIES = (
    "steps",        # device steps, pre-fusion steps, fused rows saved
    "compile",      # device_compiles (steady state must stay fixed)
    "wire",         # replication bytes by lane + bytes/op
    "ckpt",         # checkpoint bytes per evict kind, evictions/restores
    "admission",    # admission/codec rejects, admitted counts
    "trace",        # trace event volume, post-mortem bundle counts
    "touched-rows", # blocked-lanes cost-model replay of the tick trace
    "fuse",         # generalized step-fusion accounting
    "hlo",          # static compiled-HLO costs (collectives/flops/bytes)
    "wall",         # device-cell wall histograms (silicon re-record only)
    "flow",         # per-op provenance: span terminal states + op-age-
    #                 at-apply in logical ticks (obs/flow, ISSUE 11) —
    #                 the ROADMAP-7 pipelined-tick latency contract
    "recovery",     # durability (ISSUE 16): journal bytes/op, replayed
    #                 records/ops/ticks-to-recover of the pinned crash
    #                 scenario, byte-identity + crash-audit asserted
    #                 green before pinning
)

CELL_KINDS = ("cpu", "device")


def metric(value, family: str, tol: float = 0.0) -> dict:
    """One ledger metric entry. ``tol`` is a RELATIVE band: 0.0 pins the
    value exactly (logical counters), ``0.5`` accepts ±50% (HLO costs,
    which drift with compiler versions without a logic change)."""
    assert family in METRIC_FAMILIES, family
    assert tol >= 0.0
    v = float(value)
    out = {"v": int(v) if v == int(v) and tol == 0.0 else round(v, 6),
           "family": family}
    if tol:
        out["tol"] = tol
    return out


def validate_ledger(ledger: dict) -> None:
    """Raise ``ValueError`` naming every schema violation — the same
    write-time strictness as ``bench.validate_row``: a drifted artifact
    must refuse loudly, not mis-compare quietly."""
    problems: List[str] = []
    if ledger.get("schema_version") != LEDGER_SCHEMA_VERSION:
        problems.append(
            f"schema_version {ledger.get('schema_version')!r} != "
            f"{LEDGER_SCHEMA_VERSION} (re-record through "
            f"perf/cost_ledger_probe.py)")
    cells = ledger.get("cells")
    if not isinstance(cells, dict) or not cells:
        problems.append("ledger carries no cells")
        cells = {}
    for name, cell in cells.items():
        if cell.get("kind") not in CELL_KINDS:
            problems.append(f"cell {name!r}: unknown kind "
                            f"{cell.get('kind')!r}")
        if not isinstance(cell.get("workload"), dict):
            problems.append(f"cell {name!r}: missing workload pin")
        metrics = cell.get("metrics")
        if not isinstance(metrics, dict) or not metrics:
            problems.append(f"cell {name!r}: no metrics")
            continue
        for mname, m in metrics.items():
            if not isinstance(m, dict) or "v" not in m:
                problems.append(f"metric {name}.{mname}: no value")
                continue
            if not isinstance(m["v"], (int, float)):
                problems.append(f"metric {name}.{mname}: non-numeric "
                                f"value {m['v']!r}")
            if m.get("family") not in METRIC_FAMILIES:
                problems.append(f"metric {name}.{mname}: unknown family "
                                f"{m.get('family')!r}")
            if m.get("tol") is not None and (
                    not isinstance(m["tol"], (int, float))
                    or m["tol"] < 0):
                problems.append(f"metric {name}.{mname}: bad tol "
                                f"{m.get('tol')!r}")
    if problems:
        raise ValueError("cost ledger violates the schema: "
                         + "; ".join(problems))


def families_covered(ledger: dict) -> set:
    return {m.get("family")
            for cell in ledger.get("cells", {}).values()
            for m in cell.get("metrics", {}).values()}


def diff_cell(name: str, committed: dict, fresh: dict) -> List[str]:
    """Named per-metric diffs between one committed cell and its fresh
    re-derivation.  Drift in EITHER direction is a finding: a value
    outside its band, a committed metric the code no longer produces,
    or a new metric the ledger never recorded (schema growth that needs
    a deliberate re-record, not a silent pass)."""
    out: List[str] = []
    cm = committed.get("metrics", {})
    fm = fresh.get("metrics", {})
    for mname in sorted(cm):
        if mname not in fm:
            out.append(f"{name}.{mname}: committed "
                       f"{cm[mname]['v']} but the probe no longer "
                       f"derives it (re-record the ledger if deliberate)")
            continue
        want, got = cm[mname]["v"], fm[mname]["v"]
        tol = cm[mname].get("tol", 0.0)
        if tol:
            band = abs(want) * tol
            if abs(got - want) > band:
                out.append(
                    f"{name}.{mname} [{cm[mname]['family']}]: "
                    f"{got} outside {want} ±{tol * 100:.0f}% "
                    f"(band ±{band:.6g})")
        elif got != want:
            out.append(
                f"{name}.{mname} [{cm[mname]['family']}]: "
                f"{got} != committed {want} (exact logical counter)")
    for mname in sorted(set(fm) - set(cm)):
        out.append(f"{name}.{mname}: derived {fm[mname]['v']} but the "
                   f"committed ledger never recorded it (re-record to "
                   f"adopt the new metric)")
    return out


def _without_hlo(cell: dict) -> dict:
    return dict(cell, metrics={k: m for k, m in cell.get("metrics",
                                                         {}).items()
                               if m.get("family") != "hlo"})


def diff_ledger(committed: dict, fresh_cells: Dict[str, dict],
                jax_version: Optional[str] = None
                ) -> Tuple[bool, List[str]]:
    """Compare committed cells against freshly derived ones; only cells
    present in ``fresh_cells`` are judged (the gate derives the cpu
    cells; device cells are recorded on the chip).  Returns (ok, named
    diffs).

    ``hlo`` metrics are what the compiler emits, so they are only
    comparable under the jax the ledger was recorded with: when
    ``jax_version`` differs from ``recorded.jax``, each cell's hlo
    metrics yield one "re-record" finding instead of drift diffs (the
    logical counters are still compared exactly).
    """
    diffs: List[str] = []
    cells = committed.get("cells", {})
    recorded = committed.get("recorded", {}).get("jax")
    other_jax = jax_version is not None and jax_version != recorded
    for name in sorted(fresh_cells):
        if name not in cells:
            diffs.append(f"{name}: derived a cell the committed ledger "
                         f"does not carry (re-record to adopt it)")
            continue
        want, got = cells[name], fresh_cells[name]
        if other_jax and want != _without_hlo(want):
            diffs.append(f"{name}: hlo metrics recorded under jax "
                         f"{recorded}, installed jax {jax_version} — "
                         f"re-record (python perf/cost_ledger_probe.py "
                         f"--cells {name})")
            want, got = _without_hlo(want), _without_hlo(got)
        diffs.extend(diff_cell(name, want, got))
    return not diffs, diffs


def cpu_cell_names(ledger: dict) -> List[str]:
    """The cells the wall-clock-free gate can re-derive on any box."""
    return sorted(n for n, c in ledger.get("cells", {}).items()
                  if c.get("kind") == "cpu")


def load_ledger(path: str = LEDGER_PATH) -> dict:
    with open(path) as f:
        return json.load(f)
