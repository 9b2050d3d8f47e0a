"""tcrlint v2 self-tests (ISSUE 15): the dataflow engine + the four
interprocedural check families + the incremental gate.

Same proof obligations as PR 12's per-family suite, now for flow-aware
checks: every family proven LOUD by seeded-defect injection (exit-1 /
finding naming the exact file:line + check id) and QUIET on the clean
tree — with the real serve files as the known-clean corpus (the
runtime sanitizer's sites), and real-file mutations (a mirror update
deleted from the committed ``FlatLaneBackend.apply``) as the seeded
defects.  Plus the incremental machinery: content-hash cache
hit/invalidation, ``--changed`` against a real git merge-base, and the
ruff-parity pin for the F401 fallback floor.
"""
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from text_crdt_rust_tpu.analysis import run_lint
from text_crdt_rust_tpu.analysis.checks_shape import (
    SHAPE_PINS_PATH,
    harvest_contracts,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(tmp_path, files, allow=None, shape_pins=None, **kw):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    allow_path = str(tmp_path / "allow.json")
    if allow is not None:
        (tmp_path / "allow.json").write_text(json.dumps({"allow": allow}))
    return run_lint(str(tmp_path), allowlist_path=allow_path,
                    pins_path=str(tmp_path / "pins.json"),
                    shape_pins_path=shape_pins or str(
                        tmp_path / "shape_pins.json"), **kw)


def the(findings, check):
    hits = [f for f in findings if f.check == check]
    assert hits, f"no {check} finding in {[f.format() for f in findings]}"
    return hits


def none_of(findings, check):
    hits = [f.format() for f in findings if f.check == check]
    assert not hits, hits


# ------------------------------------------------ the dataflow engine -------


def _flow(src, name):
    import ast

    from text_crdt_rust_tpu.analysis.dataflow import FunctionFlow

    tree = ast.parse(textwrap.dedent(src))
    for node in ast.walk(tree):
        if getattr(node, "name", None) == name:
            return FunctionFlow(node)
    raise AssertionError(name)


def test_cfg_loop_back_edge_reaches_earlier_statement():
    flow = _flow("""\
        def f(xs):
            for x in xs:
                a = 1
                b = 2
            return a
        """, "f")
    # stmts: for(0), a=1(1), b=2(2), return(3)
    reach = flow.reachable_from(1)
    assert 1 in reach and 2 in reach and 3 in reach  # via the back edge


def test_cfg_sync_statement_blocks_propagation():
    flow = _flow("""\
        def f(backend, s):
            backend.apply(s)
            backend.barrier()
            s.pos[0] = 1
        """, "f")
    from text_crdt_rust_tpu.analysis.checks_pipeline import _is_sync_stmt

    sync = {i for i, s in enumerate(flow.stmts) if _is_sync_stmt(s)}
    assert sync == {1}
    assert 2 not in flow.reachable_from(0, blocked=sync)


def test_reaching_defs_const_resolution():
    flow = _flow("""\
        def f(cond):
            a = 48
            b = 48 if cond else 7
            use(a)
            use(b)
        """, "f")
    import ast

    uses = [s for s in flow.stmts if isinstance(s, ast.Expr)]
    a_arg = uses[0].value.args[0]
    b_arg = uses[1].value.args[0]
    assert flow.const_int(a_arg, flow.index[uses[0]]) == 48
    # b's definition is not a plain literal binding -> unresolved
    assert flow.const_int(b_arg, flow.index[uses[1]]) is None


def test_const_resolution_refuses_conflicting_defs():
    flow = _flow("""\
        def f(cond):
            if cond:
                a = 8
            else:
                a = 48
            use(a)
        """, "f")
    import ast

    use = [s for s in flow.stmts if isinstance(s, ast.Expr)][0]
    assert flow.const_int(use.value.args[0], flow.index[use]) is None


def test_alias_closure_chases_stack_and_pad():
    flow = _flow("""\
        def f(streams, apply):
            per_lane = [pad_ops(s, 8) for s in streams]
            stacked = stack_ops(per_lane)
            apply(stacked)
        """, "f")
    import ast

    call = [s for s in flow.stmts if isinstance(s, ast.Expr)][-1]
    taint, containers = flow.alias_closure(
        call.value.args, flow.index[call])
    assert {"stacked", "per_lane", "streams"} <= taint
    assert "per_lane" in containers  # list-comp constructed


def test_summaries_mark_mutating_params():
    import ast

    from text_crdt_rust_tpu.analysis.dataflow import summarize_module

    tree = ast.parse(textwrap.dedent("""\
        import numpy as np


        def scrub(a, b):
            a[0] = 0
            return b


        def reader(a):
            return a.sum()


        class K:
            def touch(self):
                self._n_host[0] = 1
        """))
    s = summarize_module(tree)
    assert s["scrub"].mutated_params == ("a",)
    assert s["reader"].mutated_params == ()
    assert "_n_host" in s["K.touch"].writes_self_attrs


# ------------------------------------------- family TCR-P: pipeline escape --




def test_post_dispatch_mutation_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": "import numpy as np\n\n\n" + textwrap.dedent("""\
        def tick(backend, stacked):
            backend.apply(stacked)
            stacked.pos[0] = 7
        """)})
    f = the(findings, "TCR-P001")[0]
    assert (f.path, f.line) == ("mod.py", 6)
    assert "dispatched at line 5" in f.message


def test_mutation_after_staged_sync_passes(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": "import numpy as np\n\n\n" + textwrap.dedent("""\
        def tick(backend, stacked):
            backend.apply(stacked)
            backend.barrier()
            stacked.pos[0] = 7
        """)})
    none_of(findings, "TCR-P001")


def test_interprocedural_mutation_via_helper_flagged(tmp_path):
    """One-level call summaries: the mutation hides in a same-module
    helper the post-dispatch code hands the buffer to."""
    findings, _ = lint_tree(tmp_path, {"mod.py": "import numpy as np\n\n\n" + textwrap.dedent("""\
        def scrub(a):
            a[0] = 0


        def tick(backend, stacked):
            backend.apply(stacked)
            scrub(stacked.pos)
        """)})
    f = the(findings, "TCR-P001")[0]
    assert f.line == 10


def test_forward_alias_and_copyto_flagged(tmp_path):
    """A post-dispatch binding that aliases the dispatched buffer
    (subscript read) is tainted; np.copyto through it is a finding."""
    findings, _ = lint_tree(tmp_path, {"mod.py": "import numpy as np\n\n\n" + textwrap.dedent("""\
        def tick(backend, per_lane):
            stacked = stack_ops(per_lane)
            backend.apply(stacked)
            col = per_lane[0]
            np.copyto(col, 0)
        """)})
    f = the(findings, "TCR-P001")[0]
    assert f.line == 8


def test_loop_back_edge_mutation_flagged_once(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": "import numpy as np\n\n\n" + textwrap.dedent("""\
        def tick(backend, streams):
            for s in streams:
                backend.apply(s)
                s.chars.fill(0)
        """)})
    assert len(the(findings, "TCR-P001")) == 1


def test_container_slot_rebind_and_self_state_pass(tmp_path):
    """The two deliberate calibrations: dict/list slot rebinds are not
    array writes, and self-rooted bookkeeping is TCR-M's contract."""
    findings, _ = lint_tree(tmp_path, {"mod.py": "import numpy as np\n\n\n" + textwrap.dedent("""\
        def tick(self, backend, lane_streams):
            stacked = stack_ops(
                [pad_ops(s, 8) for s in lane_streams.values()])
            backend.apply(stacked)
            lane_streams[0] = None
            self.counters["ticks"] += 1
        """)})
    none_of(findings, "TCR-P001")


def test_real_serve_tick_is_the_known_clean_corpus():
    """The runtime sanitizer's known-clean sites (the real batcher +
    lanes backend, every dispatch edge of the serve tick) lint quiet —
    the seed corpus of ISSUE 15."""
    findings, _ = run_lint(
        REPO, ["text_crdt_rust_tpu/serve/batcher.py",
               "text_crdt_rust_tpu/serve/lanes_backend.py",
               "text_crdt_rust_tpu/ops/flat.py"])
    none_of(findings, "TCR-P001")


# ------------------------------------------- family TCR-M: mirror pairing ---


def _mutated_batcher(strip: str) -> str:
    src = open(os.path.join(
        REPO, "text_crdt_rust_tpu/serve/batcher.py")).read()
    assert strip in src, "seeded-defect anchor drifted"
    return src.replace(strip, "")


MIRROR_CUT = """\
        self._n_host += np.asarray(
            stacked.ins_len, dtype=np.int64).sum(axis=0)
        self._next_order_host += np.asarray(
            stacked.order_advance, dtype=np.int64).sum(axis=0)
"""

# The train-boundary mirror true-up inside _dispatch_train (ISSUE 20).
# Stripping it alongside MIRROR_CUT removes EVERY path from apply's
# device write to a mirror (direct and via the train_sync helper), so
# the M001 injection stays loud — and the same cut is the M003 seeded
# defect (the registered train_sync site no longer trues up).
TRAIN_SYNC_CUT = """\
        self._n_host = self._n_host + self._pending_n
        self._next_order_host = self._next_order_host + self._pending_o
"""


def test_mirror_skip_injection_named_by_lint(tmp_path):
    """ISSUE 15 satellite: the REAL FlatLaneBackend.apply with its
    host-mirror updates deleted — the lint names the device-write line
    and the check id (the static half; the runtime half lives in
    test_device_prefill.py).  Both mirror-advance sites go: the serial
    per-tick block AND the train-boundary true-up (which would
    otherwise excuse apply via the one-level helper rule)."""
    rel = "text_crdt_rust_tpu/serve/batcher.py"
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    src = _mutated_batcher(MIRROR_CUT)
    assert TRAIN_SYNC_CUT in src, "train true-up anchor drifted"
    p.write_text(src.replace(TRAIN_SYNC_CUT, ""))
    findings, _ = run_lint(str(tmp_path), [rel],
                           allowlist_path=str(tmp_path / "a.json"),
                           pins_path=str(tmp_path / "p.json"),
                           shape_pins_path=str(tmp_path / "sp.json"))
    hits = the(findings, "TCR-M001")
    apply_hits = [f for f in hits if "FlatLaneBackend.apply" in f.message]
    assert apply_hits, [f.format() for f in hits]
    assert apply_hits[0].scope == "FlatLaneBackend.apply"
    assert "_n_host" in apply_hits[0].message


def test_train_sync_split_injection_named_by_lint(tmp_path):
    """ISSUE 20 satellite (loud half): the REAL batcher with the
    train-boundary mirror true-up deleted from _dispatch_train — the
    registered train_sync site no longer writes a mirror in its own
    body, and TCR-M003 names the method and the atomicity contract."""
    rel = "text_crdt_rust_tpu/serve/batcher.py"
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(_mutated_batcher(TRAIN_SYNC_CUT))
    findings, _ = run_lint(str(tmp_path), [rel],
                           allowlist_path=str(tmp_path / "a.json"),
                           pins_path=str(tmp_path / "p.json"),
                           shape_pins_path=str(tmp_path / "sp.json"))
    hits = the(findings, "TCR-M003")
    assert hits, "train_sync cut not flagged"
    assert hits[0].scope == "FlatLaneBackend._dispatch_train"
    assert "atomic" in hits[0].message


def test_train_sync_delegation_flagged_even_when_m001_passes(tmp_path):
    """TCR-M003 is strictly stronger than M001 at the train boundary: a
    train_sync site that delegates its mirror true-up to a same-class
    helper passes M001's one-level rule but still fails M003 (the
    true-up must be in the SAME method as the device write)."""
    findings, _ = lint_tree(tmp_path, {
        "text_crdt_rust_tpu/serve/mod.py": """\
            class FlatLaneBackend:
                def _true_up(self):
                    self._n_host = self._n_host + self._pending_n

                def _dispatch_train(self):
                    self.docs = self.docs.at[0].set(0)
                    self._true_up()
            """})
    none_of(findings, "TCR-M001")
    hits = the(findings, "TCR-M003")
    assert hits and hits[0].scope == "FlatLaneBackend._dispatch_train"


def test_clean_tree_has_no_train_sync_findings():
    """ISSUE 20 satellite (quiet half): the committed batcher's
    _dispatch_train satisfies the atomic train_sync contract."""
    findings, _ = run_lint(
        REPO, ["text_crdt_rust_tpu/serve/batcher.py"])
    none_of(findings, "TCR-M003")


def test_clean_backends_pass_with_committed_allowlist():
    findings, _ = run_lint(
        REPO, ["text_crdt_rust_tpu/serve/batcher.py",
               "text_crdt_rust_tpu/serve/lanes_backend.py"])
    none_of(findings, "TCR-M001")
    none_of(findings, "TCR-M002")


def test_rank_only_rewrite_carries_a_scoped_grant():
    """remap_lane_ranks writes device state with NO mirror — correct by
    construction (occupancy untouched) and therefore exactly the shape
    that must be a justified allowlist grant, not silence."""
    from text_crdt_rust_tpu.analysis.tcrlint import load_allowlist

    grants = [e for e in load_allowlist()
              if e["check"] == "TCR-M001"
              and e["scope"] == "FlatLaneBackend.remap_lane_ranks"]
    assert grants and "rank" in grants[0]["why"].lower()


def test_unregistered_serve_backend_class_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {
        "text_crdt_rust_tpu/serve/newbackend.py": """\
            class ShinyLaneBackend:
                def clear_lane(self, b):
                    self.docs = self.docs.at[b].set(0)
            """})
    f = the(findings, "TCR-M002")[0]
    assert f.line == 3 and "MIRROR_CONTRACTS" in f.message


def test_mirror_paired_via_same_class_helper_passes(tmp_path):
    """One-level pairing: the mirror update may live in a helper
    method the write site calls."""
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        class FlatLaneBackend:
            def _bump(self, b):
                self._n_host[b] += 1

            def clear_lane(self, b):
                self.docs = self.docs.at[b].set(0)
                self._bump(b)
        """})
    none_of(findings, "TCR-M001")


# ------------------------------------------- family TCR-K: shape contracts --


def test_off_series_literal_and_const_prop_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        def stage(stream, pad_ops):
            bkt = 48
            ok = pad_ops(stream, 8)
            bad = pad_ops(stream, 48)
            worse = pad_ops(stream, bkt)
            dyn = pad_ops(stream, len(stream))
            return ok, bad, worse, dyn
        """}, shape_pins=SHAPE_PINS_PATH)
    hits = the(findings, "TCR-K001")
    assert [f.line for f in hits] == [4, 5]
    assert "step-bucket series" in hits[0].message


def test_off_series_scatter_bucket_flagged(tmp_path):
    findings, _ = lint_tree(tmp_path, {"mod.py": """\
        def build(PrefillDelta, cols):
            good = PrefillDelta(*cols, bucket=128)
            bad = PrefillDelta(*cols, bucket=100)
            return good, bad
        """}, shape_pins=SHAPE_PINS_PATH)
    hits = the(findings, "TCR-K001")
    assert [f.line for f in hits] == [3]
    assert "scatter-bucket series" in hits[0].message


def test_shape_contracts_pin_matches_live_tree():
    """The committed SHAPE_CONTRACTS.json agrees with the harvested
    series — the shipped tree carries no unpinned shape drift, and the
    harvest itself sees the real surfaces."""
    live = harvest_contracts(REPO)
    pinned = json.load(open(SHAPE_PINS_PATH))["contracts"]
    assert live == pinned
    assert live["scatter-series"]["base"] == 32
    assert live["scatter-series"]["factor"] == 4
    assert live["step-buckets"]["buckets"] == [8, 32, 128]
    assert live["smem-op-columns"]["text_crdt_rust_tpu/ops/rle.py"] == 5


def test_shape_series_drift_without_repin_flagged(tmp_path):
    """Mutate a pinned series copy -> TCR-K002 naming the declaring
    file and demanding --update-pins in the same change."""
    pins = json.load(open(SHAPE_PINS_PATH))
    pins["contracts"]["step-buckets"]["buckets"] = [8, 32]
    mutated = tmp_path / "shape_pins.json"
    mutated.write_text(json.dumps(pins))
    findings, _ = run_lint(
        REPO, ["text_crdt_rust_tpu/analysis/checks_shape.py"],
        shape_pins_path=str(mutated))
    f = the(findings, "TCR-K002")[0]
    assert f.path == "text_crdt_rust_tpu/config.py"
    assert "--update-pins" in f.message


def test_update_pins_rewrites_shape_contracts(tmp_path):
    out = tmp_path / "shape_pins.json"
    findings, _ = run_lint(
        REPO, ["text_crdt_rust_tpu/analysis/checks_shape.py"],
        shape_pins_path=str(out), update_pins=True,
        pins_path=str(tmp_path / "schema_pins.json"))
    assert json.load(open(out))["contracts"] == \
        json.load(open(SHAPE_PINS_PATH))["contracts"]


# ------------------------------------------- family TCR-C: claims ----------


CLAIMS_TREE = {
    "README.md": """\
        # x
        ## Measured vs pending silicon
        | claim | status | evidence |
        |---|---|---|
        | good row | **measured** | `perf/real_r1.json` |
        | ghost row | **measured** | `perf/ghost_r9.json` |
        | sourceless | measured on CPU | trust me |
        | speed row | not measured on the current tree | — |

        ## History
        `perf/real_r1.json` named in narrative is fine.
        """,
    "PERF.md": "see `perf/missing_probe.py`\n",
    "perf/real_r1.json": "{}",
}


def test_claims_findings_name_rotted_evidence(tmp_path):
    findings, _ = lint_tree(tmp_path, dict(CLAIMS_TREE))
    c1 = the(findings, "TCR-C001")
    assert {(f.path, f.line) for f in c1} == {("README.md", 6),
                                             ("PERF.md", 1)}
    c3 = the(findings, "TCR-C003")
    # The "not measured" row (line 8) claims nothing: no finding.
    assert {f.line for f in c3} == {6, 7}
    none_of(findings, "TCR-C002")  # the watcher check is gone


def test_claims_clean_when_artifacts_committed(tmp_path):
    tree = dict(CLAIMS_TREE)
    tree["README.md"] = """\
        # x
        ## Measured vs pending silicon
        | claim | status | evidence |
        |---|---|---|
        | good row | **measured** | `perf/real_r1.json` |
        | speed row | not measured on the current tree | — |
        """
    tree["PERF.md"] = "see `perf/real_r1.json`\n"
    findings, _ = lint_tree(tmp_path, tree)
    for check in ("TCR-C001", "TCR-C003"):
        none_of(findings, check)


def test_real_repo_claims_are_consistent():
    """The shipped README/PERF cite only committed artifacts."""
    from text_crdt_rust_tpu.analysis.checks_claims import check_claims

    assert [f.format() for f in check_claims(REPO)] == []


# ------------------------------------------- incremental: cache + changed ---


def test_cache_second_run_hits_and_mutation_invalidates(tmp_path):
    files = {"mod.py": "X = 1\n", "other.py": "Y = 2\n"}
    _, s1 = lint_tree(tmp_path, files, use_cache=True)
    assert s1["cache"] == {"hits": 0, "misses": 2}
    _, s2 = lint_tree(tmp_path, {}, use_cache=True)
    assert s2["cache"] == {"hits": 2, "misses": 0}
    (tmp_path / "mod.py").write_text("X = 3\n")
    _, s3 = lint_tree(tmp_path, {}, use_cache=True)
    assert s3["cache"] == {"hits": 1, "misses": 1}


def test_cache_reuses_findings_faithfully(tmp_path):
    files = {"mod.py": "import time\n\n\ndef f():\n"
                       "    return time.time()\n"}
    f1, _ = lint_tree(tmp_path, files, use_cache=True)
    f2, s2 = lint_tree(tmp_path, {}, use_cache=True)
    assert s2["cache"]["hits"] == 1
    assert [f.format() for f in f1] == [f.format() for f in f2]


def test_cache_invalidated_by_allowlist_change(tmp_path):
    """The config digest folds in the allowlist: granting a finding
    must not serve the stale cached verdict."""
    files = {"mod.py": "import time\n\n\ndef f():\n"
                       "    return time.time()\n"}
    f1, _ = lint_tree(tmp_path, files, use_cache=True)
    assert the(f1, "TCR-W001")
    f2, s2 = lint_tree(
        tmp_path, {}, use_cache=True,
        allow=[{"check": "TCR-W001", "path": "mod.py", "scope": "f",
                "why": "test probe grant for the cache invalidation"}],
        check_stale_allowlist=False)
    assert s2["cache"]["misses"] == 1  # digest changed -> re-lint
    none_of(f2, "TCR-W001")


def _git(cwd, *args):
    return subprocess.run(["git", "-C", str(cwd), *args],
                          capture_output=True, text=True, check=True)


def test_changed_files_against_a_real_merge_base(tmp_path):
    """--changed in a scratch git repo: only the edited file is
    selected, and the CLI lints exactly it."""
    if shutil.which("git") is None:
        pytest.skip("no git in container")
    repo = tmp_path / "r"
    repo.mkdir()
    _git(repo, "init", "-q", "-b", "main")
    _git(repo, "config", "user.email", "t@t")
    _git(repo, "config", "user.name", "t")
    (repo / "clean.py").write_text("A = 1\n")
    (repo / "dirty.py").write_text("B = 2\n")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "seed")
    (repo / "dirty.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    from text_crdt_rust_tpu.analysis.tcrlint import changed_files

    assert changed_files(str(repo)) == ["dirty.py"]
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--root", str(repo), "--changed", "HEAD", "--no-cache",
         "--allowlist", str(repo / "none.json"),
         "--pins", str(repo / "none_pins.json"),
         "--shape-pins", str(repo / "none_shape.json"),
         "--json", "dirty.py", "clean.py"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    out = json.loads(r.stdout)
    assert r.returncode == 1
    assert out["stats"]["files"] == 1  # clean.py not re-linted
    assert any("dirty.py:5: TCR-W001" in f for f in out["findings"])


def test_changed_mode_without_git_falls_back_to_full(tmp_path):
    (tmp_path / "mod.py").write_text("A = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--root", str(tmp_path), "--changed", "--no-cache",
         "--allowlist", str(tmp_path / "none.json"),
         "--pins", str(tmp_path / "none_pins.json"),
         "--shape-pins", str(tmp_path / "none_shape.json"),
         "--json", "mod.py"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    out = json.loads(r.stdout)
    assert out["stats"]["files"] == 1
    assert "fell back" in out["stats"]["mode"]


# ------------------------------------------- ruff F401 parity (satellite) ---


F401_FIXTURE = {
    "pkg/__init__.py": "from .mod_a import used_fn\n",
    "pkg/mod_a.py": """\
        import json
        import os  # noqa
        import sys
        from collections import OrderedDict, deque

        __all__ = ["deque"]


        def used_fn():
            return sys.argv
        """,
    "pkg/mod_b.py": "import zlib\n\nCRC = zlib.crc32(b'x')\n",
}

#: The pinned F401 floor on the fixture tree: (path, line, name).
#: __init__.py is exempt (re-export surface; mirrored in the ruff run
#: by pyproject's per-file-ignores), the noqa line is honored, __all__
#: membership is a use.
F401_EXPECTED = {
    ("pkg/mod_a.py", 1, "json"),
    ("pkg/mod_a.py", 4, "OrderedDict"),
}


def _fallback_findings(tmp_path):
    findings, _ = run_lint(str(tmp_path),
                           allowlist_path=str(tmp_path / "a.json"),
                           pins_path=str(tmp_path / "p.json"),
                           shape_pins_path=str(tmp_path / "sp.json"))
    out = set()
    for f in findings:
        if f.check != "TCR-F401":
            continue
        m = re.match(r"'([^']+)'", f.message)
        out.add((f.path, f.line, m.group(1)))
    return out


def test_f401_fallback_floor_is_pinned(tmp_path):
    """The container-dependent gate floor, pinned: the built-in
    fallback reports EXACTLY this finding set on the seeded fixture."""
    for rel, src in F401_FIXTURE.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    assert _fallback_findings(tmp_path) == F401_EXPECTED


def test_f401_fallback_matches_ruff_when_installed(tmp_path):
    """Parity with the real ruff F401 on the same fixture — the half
    that only runs where ruff exists; the pinned-floor test above
    keeps the contract checkable in ruff-less containers."""
    if shutil.which("ruff") is None:
        pytest.skip("ruff not installed — floor pinned by the "
                    "fallback test")
    for rel, src in F401_FIXTURE.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    r = subprocess.run(
        ["ruff", "check", "--isolated", "--select", "F401",
         "--per-file-ignores", "__init__.py:F401",
         "--output-format", "concise", "."],
        capture_output=True, text=True, cwd=tmp_path, timeout=120)
    got = set()
    for line in r.stdout.splitlines():
        m = re.match(r"(.+?):(\d+):\d+: F401 .*`([^`]+)`", line)
        if m:
            name = m.group(3).split(".")[-1]
            got.add((m.group(1).replace(os.sep, "/"),
                     int(m.group(2)), name))
    assert got == F401_EXPECTED


# ------------------------------------------- the incremental tier-1 gate ----


def test_lint_gate_incremental_under_budget():
    """ISSUE 15 acceptance: the tier-1 gate's incremental mode —
    ``--changed`` against the merge-base, warm cache — exits 0 on the
    clean tree in < 15 s (the full-tree clean proof lives in
    test_analysis_lint.py's gate test).  ``TCR_LINT_FULL=1`` is the
    weekly-style fallback knob: it drops ``--changed`` and forces the
    full walk through this same gate."""
    argv = [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
            "--json"]
    if not os.environ.get("TCR_LINT_FULL"):
        argv.insert(-1, "--changed")
    t0 = time.perf_counter()
    r = subprocess.run(argv, capture_output=True, text=True,
                       timeout=120, cwd=REPO)
    wall = time.perf_counter() - t0
    assert r.returncode == 0, (r.stdout[-4000:], r.stderr[-2000:])
    out = json.loads(r.stdout)
    assert out["ok"]
    assert wall < 15, f"incremental gate took {wall:.1f}s (budget 15s)"


def test_lint_gate_loud_through_cli_on_v2_families(tmp_path):
    """ONE violating tree exercises all four v2 families through the
    real CLI: exit 1, each finding file:line-named on stdout."""
    (tmp_path / "perf").mkdir()
    (tmp_path / "bad.py").write_text(textwrap.dedent("""\
        def tick(backend, stacked, pad_ops):
            backend.apply(stacked)
            stacked.pos[0] = 7
            return pad_ops(stacked, 48)
        """))
    (tmp_path / "README.md").write_text(textwrap.dedent("""\
        ## Measured vs pending silicon
        | claim | status | evidence |
        |---|---|---|
        | ghost | **measured** | `perf/ghost.json` |
        """))
    (tmp_path / "text_crdt_rust_tpu" / "serve").mkdir(parents=True)
    (tmp_path / "text_crdt_rust_tpu" / "serve" / "nb.py").write_text(
        textwrap.dedent("""\
            class NewBackend:
                def seed(self, b):
                    self.state = self.state.at[b].set(0)
            """))
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--root", str(tmp_path), "--no-cache",
         "--allowlist", str(tmp_path / "none.json"),
         "--pins", str(tmp_path / "none_pins.json"),
         "--shape-pins", SHAPE_PINS_PATH,
         "bad.py", "text_crdt_rust_tpu"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "bad.py:3: TCR-P001" in r.stdout
    assert "bad.py:4: TCR-K001" in r.stdout
    assert "README.md:4: TCR-C001" in r.stdout
    assert "README.md:4: TCR-C003" in r.stdout
    assert "nb.py:3: TCR-M002" in r.stdout


def test_sync_inside_a_branch_does_not_mask_other_branches(tmp_path):
    """Review hardening: a compound statement CONTAINING a sync call in
    one branch is not itself a sync — the mutation on the other branch
    still races the dispatch and must stay loud (only the bare sync
    statement blocks its own successors)."""
    findings, _ = lint_tree(tmp_path, {"mod.py": textwrap.dedent("""\
        def tick(backend, stacked, flag):
            backend.apply(stacked)
            if flag:
                backend.barrier()
            else:
                stacked.pos[0] = 1
        """)})
    f = the(findings, "TCR-P001")[0]
    assert f.line == 6
    # ...and the straight-line sync still kills propagation: the same
    # mutation AFTER the if (both paths joined past a barrier on one
    # side only) is still reachable via the else path.
    findings2, _ = lint_tree(tmp_path, {"mod2.py": textwrap.dedent("""\
        def tick(backend, stacked):
            backend.apply(stacked)
            backend.barrier()
            stacked.pos[0] = 1
        """)})
    none_of([f for f in findings2 if f.path == "mod2.py"], "TCR-P001")


def test_changed_mode_summary_source_edit_forces_full_walk(tmp_path):
    """Review hardening: a changed interprocedural summary source
    (ops/flat.py & co) can induce findings in UNCHANGED dependents, so
    --changed must widen to the full walk, not lint the source alone."""
    if shutil.which("git") is None:
        pytest.skip("no git in container")
    repo = tmp_path / "r"
    (repo / "text_crdt_rust_tpu" / "ops").mkdir(parents=True)
    _git(repo, "init", "-q", "-b", "main")
    _git(repo, "config", "user.email", "t@t")
    _git(repo, "config", "user.name", "t")
    (repo / "text_crdt_rust_tpu" / "ops" / "flat.py").write_text("A = 1\n")
    (repo / "dependent.py").write_text("B = 2\n")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-qm", "seed")
    (repo / "text_crdt_rust_tpu" / "ops" / "flat.py").write_text("A = 3\n")
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--root", str(repo), "--changed", "HEAD", "--no-cache",
         "--allowlist", str(repo / "none.json"),
         "--pins", str(repo / "none_pins.json"),
         "--shape-pins", str(repo / "none_shape.json"),
         "--json", "text_crdt_rust_tpu", "dependent.py"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    out = json.loads(r.stdout)
    assert "summary source" in out["stats"]["mode"]
    assert out["stats"]["files"] == 2  # the full target set, not 1


def test_try_else_block_is_flow_reachable(tmp_path):
    """Review hardening: the try body falls through to its else block
    (which runs exactly when no exception fired) — a post-dispatch
    mutation there must not be a CFG orphan."""
    findings, _ = lint_tree(tmp_path, {"mod.py": textwrap.dedent("""\
        def tick(backend, stacked):
            try:
                backend.apply(stacked)
            except ValueError:
                pass
            else:
                stacked.pos[0] = 1
        """)})
    f = the(findings, "TCR-P001")[0]
    assert f.line == 7


def test_keyword_shape_argument_checked_like_positional(tmp_path):
    """Review hardening: pad_ops' keyword spelling (num_steps=) goes
    through the same TCR-K001 resolution as the positional form."""
    findings, _ = lint_tree(tmp_path, {"mod.py": textwrap.dedent("""\
        def stage(stream, pad_ops):
            ok = pad_ops(stream, num_steps=32)
            bad = pad_ops(stream, num_steps=48)
            return ok, bad
        """)}, shape_pins=SHAPE_PINS_PATH)
    hits = the(findings, "TCR-K001")
    assert [f.line for f in hits] == [3]


def test_changed_with_bad_explicit_base_is_a_usage_error():
    """Review hardening: a typo'd --changed BASE exits 2 with a usage
    error instead of silently full-walking with a wrong diagnosis."""
    r = subprocess.run(
        [sys.executable, "-m", "text_crdt_rust_tpu.analysis.lint",
         "--changed", "no-such-ref-xyz", "--no-cache", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 2
    assert "usage error" in r.stderr and "no-such-ref-xyz" in r.stderr
