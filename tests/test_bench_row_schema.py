"""Bench-row exporter schema (ISSUE 8 satellite).

Every non-error row in the committed ``BENCH_ALL.json`` must validate
against ``bench.ROW_SCHEMA`` — the shared floor that keeps rows
comparable across re-records — and the write paths (``RowSink.add``,
``merge_config_rows``) must refuse shape-drifted rows instead of
silently splitting the table into incomparable halves."""
import json
import os

import pytest

from bench import (
    ROW_SCHEMA,
    ROW_SCHEMA_VERSION,
    merge_config_rows,
    validate_row,
)
from text_crdt_rust_tpu.obs.ledger import LEDGER_SCHEMA_VERSION


def row(**kw):
    """A schema-complete exporter row with overrides (the
    ``test_bench_rowsink.row`` fixture; tests/ is not a package, so the
    helper is duplicated rather than imported)."""
    r = {"schema_version": ROW_SCHEMA_VERSION,
         "ledger_version": LEDGER_SCHEMA_VERSION, "config": "cfg",
         "engine": "rle", "metric": "crdt_ops_per_sec_chip",
         "value": 1.0, "unit": "ops/s", "batch": 1, "ops": 1,
         "device_steps": 1, "mean_step_latency_us": 1.0,
         "hbm_bytes_accounted": 0, "hbm_bytes_measured": None,
         "vs_baseline": None, "baseline_ops_per_sec": None,
         "oracle_equal": True, "cfg_key": "k", "variant": "v"}
    r.update(kw)
    return r


def test_committed_bench_all_rows_validate():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_ALL.json")
    with open(path) as f:
        rows = json.load(f)
    assert rows, "committed BENCH_ALL.json is empty"
    for r in rows:
        validate_row(r)  # raises with the offending fields named
        if "error" not in r:
            assert r["schema_version"] == ROW_SCHEMA_VERSION


def test_additive_flow_fields_validate_without_schema_bump():
    """ISSUE 11 satellite: the serve/serve-lanes rows' flow_* fields
    (spans tracked, audit verdict, age percentiles in ticks) are
    ADDITIVE — the schema pins the floor, not the ceiling, so no
    row-schema major bump and old rows stay comparable."""
    extra = row(flow_spans=2880, flow_audit_ok=True,
                flow_age_p50_ticks=8, flow_age_p99_ticks=25)
    validate_row(extra)  # would raise on any floor violation
    assert extra["schema_version"] == ROW_SCHEMA_VERSION


def test_validate_rejects_missing_field():
    bad = row()
    del bad["metric"]
    with pytest.raises(ValueError, match="missing field 'metric'"):
        validate_row(bad)


def test_validate_rejects_type_drift():
    with pytest.raises(ValueError, match="'device_steps' has type str"):
        validate_row(row(device_steps="8"))


def test_validate_rejects_version_drift():
    with pytest.raises(ValueError, match="schema_version"):
        validate_row(row(schema_version=ROW_SCHEMA_VERSION + 1))


def test_validate_exempts_error_rows():
    validate_row({"config": "c", "error": "boom"})  # no raise


def test_schema_floor_matches_make_row():
    """Every required field is one ``bench.make_row`` emits — the
    schema can't demand what the exporter doesn't produce."""
    import inspect

    import bench

    src = inspect.getsource(bench.make_row)
    for field in ROW_SCHEMA:
        if field in ("cfg_key", "variant"):  # stamped by the sinks
            continue
        assert f'"{field}"' in src, (
            f"ROW_SCHEMA requires {field!r} but make_row never emits it")


def test_rows_carry_and_enforce_ledger_version(tmp_path):
    """ISSUE 10 satellite: rows are stamped with the cost-ledger schema
    they were recorded against, and ``--merge-rows`` refuses rows from
    a drifted ledger schema (their counters no longer mean what the
    committed ledger's do)."""
    validate_row(row())  # current stamp passes
    with pytest.raises(ValueError, match="ledger_version"):
        validate_row(row(ledger_version=LEDGER_SCHEMA_VERSION + 1))
    with pytest.raises(ValueError, match="missing field 'ledger_version'"):
        bad = row()
        del bad["ledger_version"]
        validate_row(bad)
    p = str(tmp_path / "all.json")
    with pytest.raises(ValueError, match="drifted cost-ledger schema"):
        merge_config_rows(
            p, "kevin", [row(ledger_version=LEDGER_SCHEMA_VERSION + 1)],
            "v")
    assert not os.path.exists(p)  # nothing written


def test_merge_rows_refuses_shape_drifted_rows(tmp_path):
    """The ISSUE-8 gate: ``--merge-rows`` must not merge a row that
    dropped schema fields (the silent-drift failure mode)."""
    p = str(tmp_path / "all.json")
    drifted = row(value=9)
    del drifted["device_steps"]
    with pytest.raises(ValueError, match="device_steps"):
        merge_config_rows(p, "kevin", [drifted], "v")
    assert not os.path.exists(p)  # nothing written


def test_make_row_stamps_device_identity():
    """Every row names the backend of the process that produced it, so
    a CPU row can never pass for a chip row."""
    import bench

    r = bench.make_row("cfg", "rle", 10, 1, 1.0, 10, 0, None, True)
    assert (r["platform"], r["device_count"]) == ("cpu", 8)
    assert r["device_kind"]
    child = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    assert bench.make_row("cfg", "rle", 10, 1, 1.0, 10, 0, None, True,
                          device=child)["device_count"] == 1
