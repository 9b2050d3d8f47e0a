"""RowSink persistence/resume semantics (bench.py's crash-safety layer).

The driver's round-end `python bench.py` must never lose finished rows
to a mid-suite crash, resume into a different workload shape, or erase
rows it can't reuse — the exact failure modes that cost round 3 its
headline (VERDICT r3 weak #1/#2)."""
import json
import os

import pytest

from bench import ROW_SCHEMA_VERSION, RowSink
from text_crdt_rust_tpu.obs.ledger import LEDGER_SCHEMA_VERSION


def read(path):
    with open(path) as f:
        return json.load(f)


def row(**kw):
    """A schema-complete exporter row (bench.ROW_SCHEMA floor) with
    test-relevant fields overridden — fresh rows now validate at write
    time (ISSUE 8), so fixtures must carry the full floor."""
    r = {"schema_version": ROW_SCHEMA_VERSION,
         "ledger_version": LEDGER_SCHEMA_VERSION, "config": "cfg",
         "engine": "rle", "metric": "crdt_ops_per_sec_chip",
         "value": 1.0, "unit": "ops/s", "batch": 1, "ops": 1,
         "device_steps": 1, "mean_step_latency_us": 1.0,
         "hbm_bytes_accounted": 0, "hbm_bytes_measured": None,
         "vs_baseline": None, "baseline_ops_per_sec": None,
         "oracle_equal": True}
    r.update(kw)
    return r


def test_rows_persist_as_they_complete(tmp_path):
    p = str(tmp_path / "b.json")
    sink = RowSink(p, resume=False, variant="v1")
    sink.add("northstar", row(config="ns", value=1.0))
    assert [r["cfg_key"] for r in read(p)] == ["northstar"]
    sink.add("2", [row(config="a"), row(config="b")])
    assert len(read(p)) == 3  # flushed after every config


def test_resume_skips_clean_rows_same_variant(tmp_path):
    p = str(tmp_path / "b.json")
    s1 = RowSink(p, resume=False, variant="v1")
    s1.add("northstar", row(config="ns", value=1.0))
    s1.add("2", {"config": "c2", "error": "boom"})

    s2 = RowSink(p, resume=True, variant="v1")
    assert s2.done_keys == {"northstar"}     # error rows re-run
    s2.add("2", row(config="c2", value=2.0))
    rows = read(p)
    assert {r["cfg_key"] for r in rows} == {"northstar", "2"}
    # the clean rerun replaced the error row
    c2 = [r for r in rows if r["cfg_key"] == "2"]
    assert len(c2) == 1 and "error" not in c2[0]


def test_resume_preserves_keyless_same_variant_rows(tmp_path):
    """ADVICE r4: legacy/hand-edited rows without a cfg_key cannot be
    regenerated by any config re-run — resume must keep them outright,
    never silently erase them on the next flush."""
    p = str(tmp_path / "b.json")
    rows = [
        {"cfg_key": "1", "variant": "v1", "value": 1},
        {"variant": "v1", "value": 2},            # keyless same-variant
        {"variant": "v2", "value": 3},            # keyless other-variant
        {"cfg_key": "4", "variant": "v1", "error": "boom"},
    ]
    with open(p, "w") as f:
        json.dump(rows, f)
    s = RowSink(p, resume=True, variant="v1")
    s.add("4", row(value=5))
    out = read(p)
    assert sorted(r.get("value", -1) for r in out) == [1, 2, 3, 5]
    assert not any("error" in r for r in out)


def test_resume_rejects_other_variant_but_preserves_rows(tmp_path):
    """A smoke row must not satisfy a full-size resume, and resuming
    with different flags must not erase results it can't reuse."""
    p = str(tmp_path / "b.json")
    s1 = RowSink(p, resume=False, variant="smoke=True")
    s1.add("northstar", row(config="ns", value=1.0))

    s2 = RowSink(p, resume=True, variant="smoke=False")
    assert s2.done_keys == set()
    s2.add("northstar", row(config="ns", value=9.0))
    rows = read(p)
    assert len(rows) == 2  # both variants on disk
    variants = {r["variant"] for r in rows}
    assert variants == {"smoke=True", "smoke=False"}


def test_superseded_rows_survive_until_rerun_records(tmp_path):
    """Crash window: a same-variant error row scheduled for re-run must
    stay in the file until its config ACTUALLY re-records — a crash
    before then must not have erased the only trace of the failure."""
    p = str(tmp_path / "b.json")
    s1 = RowSink(p, resume=False, variant="v1")
    s1.add("northstar", row(config="ns", value=1.0))
    s1.add("2", {"config": "c2", "error": "boom"})

    s2 = RowSink(p, resume=True, variant="v1")
    # Simulate the suite completing a DIFFERENT config first, then
    # crashing: the old error row must still be on disk.
    s2.add("3", row(config="c3", value=3.0))
    rows = read(p)
    assert any(r.get("cfg_key") == "2" and "error" in r for r in rows)
    # Once config 2 re-records, the stale error row is superseded.
    s2.add("2", row(config="c2", value=2.0))
    c2 = [r for r in read(p) if r["cfg_key"] == "2"]
    assert len(c2) == 1 and "error" not in c2[0]


def test_flush_is_atomic(tmp_path):
    """flush writes tmp-then-rename; a reader never sees a torn file."""
    p = str(tmp_path / "b.json")
    sink = RowSink(p, resume=False, variant="v")
    sink.add("k", row(config="x"))
    assert not os.path.exists(p + ".tmp")
    read(p)  # parses


# ------------------------------------------------- --merge-rows (ISSUE 5) --


def test_merge_rows_replaces_cfg_key(tmp_path):
    """Single-config re-records replace that key's prior rows in place
    (drop and re-run, without hand-editing)."""
    from bench import merge_config_rows
    p = str(tmp_path / "all.json")
    with open(p, "w") as f:
        json.dump([
            {"cfg_key": "kevin", "config": "cfg", "variant": "old",
             "value": 1},
            {"cfg_key": "kevin", "config": "cfg", "variant": "old",
             "value": 2},
            {"cfg_key": "northstar", "config": "cfg", "variant": "old",
             "value": 3},
        ], f)
    merge_config_rows(p, "kevin", [row(value=9)], "new")
    rows = read(p)
    kev = [r for r in rows if r["cfg_key"] == "kevin"]
    assert len(kev) == 1 and kev[0]["value"] == 9
    assert kev[0]["variant"] == "new"
    assert [r for r in rows if r["cfg_key"] == "northstar"]


def test_merge_rows_creates_missing_file(tmp_path):
    from bench import merge_config_rows
    p = str(tmp_path / "fresh.json")
    merge_config_rows(p, "kevin", [row(value=1)], "v")
    assert read(p)[0]["cfg_key"] == "kevin"


def test_merge_rows_refuses_smoke_over_full(tmp_path):
    """A smoke run must not silently supersede full-size rows."""
    from bench import merge_config_rows
    p = str(tmp_path / "all.json")
    with open(p, "w") as f:
        json.dump([{"cfg_key": "kevin",
                    "variant": "smoke=False,kevin_n=5000000",
                    "value": 1}], f)
    with pytest.raises(SystemExit, match="refused"):
        merge_config_rows(p, "kevin", [row(value=9)], "smoke=True",
                          smoke=True)
    assert read(p)[0]["value"] == 1  # untouched


def test_merge_rows_refuses_workload_shape_downgrade(tmp_path):
    """A shrunken workload (different config labels, e.g. --kevin-n
    100000) must not erase the full-scale silicon rows; an equal-
    workload re-record under a NEW variant (engine strategy) must."""
    from bench import merge_config_rows
    p = str(tmp_path / "all.json")
    with open(p, "w") as f:
        json.dump([
            {"cfg_key": "kevin", "config": "kevin_tpu_5000000",
             "variant": "v_old", "value": 1},
            {"cfg_key": "kevin", "config": "kevin_cpu_5000000",
             "variant": "v_old", "value": 2},
        ], f)
    with pytest.raises(SystemExit, match="workload shape"):
        merge_config_rows(p, "kevin",
                          [row(config="kevin_tpu_100000", value=9),
                           row(config="kevin_cpu_5000000", value=8)],
                          "v_new")
    assert len(read(p)) == 2  # untouched
    merge_config_rows(p, "kevin",
                      [row(config="kevin_tpu_5000000", value=9),
                       row(config="kevin_cpu_5000000", value=8)],
                      "v_new")
    assert {r["value"] for r in read(p)} == {8, 9}


def test_merge_rows_supersedes_error_rows(tmp_path):
    from bench import merge_config_rows
    p = str(tmp_path / "all.json")
    with open(p, "w") as f:
        json.dump([{"cfg_key": "kevin", "config": "kevin",
                    "variant": "v", "error": "boom"}], f)
    merge_config_rows(p, "kevin",
                      [row(config="kevin_tpu_2048", value=9)], "v2")
    rows = read(p)
    assert len(rows) == 1 and rows[0]["value"] == 9


def test_merge_rows_full_supersedes_smoke(tmp_path):
    """The upgrade direction must stay open: a full-size re-record
    replaces previously merged smoke rows outright."""
    from bench import merge_config_rows
    p = str(tmp_path / "all.json")
    with open(p, "w") as f:
        json.dump([{"cfg_key": "kevin", "config": "kevin_tpu_2048",
                    "variant": "smoke=True,fuse_w=8", "value": 1}], f)
    merge_config_rows(p, "kevin",
                      [row(config="kevin_tpu_5000000", value=9)],
                      "smoke=False,fuse_w=64")
    rows = read(p)
    assert len(rows) == 1 and rows[0]["value"] == 9
