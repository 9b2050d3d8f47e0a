"""Differential tests: C++ native engine vs the Python oracle.

The native engine (order-statistic treap of RLE spans) must agree with the
item-granular oracle on every observable: text, canonical merged spans,
frontier, deletes log, double-deletes log. SURVEY §4's "dual oracle"
strategy.
"""
import random

import pytest

from text_crdt_rust_tpu import LocalOp
from text_crdt_rust_tpu.models.native import NativeListCRDT
from text_crdt_rust_tpu.models.oracle import ListCRDT
from text_crdt_rust_tpu.models.sync import export_txns_since, merge_into
from text_crdt_rust_tpu.utils.testdata import load_testing_data, trace_path

ALPHABET = "abcdefghijklmnop_"


def assert_equivalent(nat: NativeListCRDT, orc: ListCRDT):
    assert nat.to_string() == orc.to_string()
    assert len(nat) == len(orc)
    assert nat.doc_spans() == orc.doc_spans()
    assert nat.frontier == orc.frontier
    assert nat.deletes_entries() == [
        (e.op_order, e.target, e.length) for e in orc.deletes
    ]
    assert nat.double_deletes_entries() == [
        (e.target, e.length, e.excess) for e in orc.double_deletes
    ]


def test_native_smoke_matches_oracle():
    nat, orc = NativeListCRDT(), ListCRDT()
    for d in (nat, orc):
        a = d.get_or_create_agent_id("seph")
        d.local_insert(a, 0, "hi")
        d.local_insert(a, 1, "yooo")
        d.local_delete(a, 0, 3)
    assert_equivalent(nat, orc)


@pytest.mark.parametrize("seed", range(8))
def test_native_local_fuzz_vs_oracle(seed):
    rng = random.Random(seed)
    nat, orc = NativeListCRDT(), ListCRDT()
    na = nat.get_or_create_agent_id("seph")
    oa = orc.get_or_create_agent_id("seph")
    for step in range(400):
        doc_len = len(orc)
        if doc_len == 0 or rng.random() < 0.5:
            pos = rng.randint(0, doc_len)
            s = "".join(rng.choice(ALPHABET)
                        for _ in range(rng.randint(1, 3)))
            nat.local_insert(na, pos, s)
            orc.local_insert(oa, pos, s)
        elif rng.random() < 0.85:
            pos = rng.randint(0, doc_len - 1)
            span = rng.randint(1, min(8, doc_len - pos))
            nat.local_delete(na, pos, span)
            orc.local_delete(oa, pos, span)
        else:
            # Mixed txn: delete + insert at the same position.
            pos = rng.randint(0, doc_len - 1)
            span = rng.randint(1, min(4, doc_len - pos))
            s = "".join(rng.choice(ALPHABET)
                        for _ in range(rng.randint(1, 2)))
            op = LocalOp(pos=pos, ins_content=s, del_span=span)
            nat.apply_local_txn(na, [op])
            orc.apply_local_txn(oa, [op])
        if step % 37 == 0:
            assert_equivalent(nat, orc)
    assert_equivalent(nat, orc)
    orc.check()


@pytest.mark.parametrize("seed", range(6))
def test_native_remote_apply_matches_oracle(seed):
    """Concurrent 3-peer oracle history, streamed into a native doc via
    apply_remote_txn — exercises remote integrate, fragmented deletes and
    double deletes on the native engine."""
    rng = random.Random(5000 + seed)
    names = ["alice", "bob", "carol"]
    peers = []
    for nm in names:
        d = ListCRDT()
        d.get_or_create_agent_id(nm)
        peers.append(d)
    for _ in range(10):
        for d in peers:
            for _ in range(rng.randint(1, 3)):
                doc_len = len(d)
                if doc_len == 0 or rng.random() < 0.55:
                    pos = rng.randint(0, doc_len)
                    s = "".join(rng.choice(ALPHABET)
                                for _ in range(rng.randint(1, 2)))
                    d.local_insert(0, pos, s)
                else:
                    pos = rng.randint(0, doc_len - 1)
                    d.local_delete(0, pos,
                                   rng.randint(1, min(6, doc_len - pos)))
        i, j = rng.sample(range(3), 2)
        merge_into(peers[i], peers[j])
        merge_into(peers[j], peers[i])
    for _ in range(2):
        for i in range(3):
            for j in range(3):
                if i != j:
                    merge_into(peers[i], peers[j])

    # Stream peer 0's full history into both a fresh oracle and a fresh
    # native doc; all three must agree.
    txns = export_txns_since(peers[0], 0)
    nat, orc = NativeListCRDT(), ListCRDT()
    for t in txns:
        nat.apply_remote_txn(t)
        orc.apply_remote_txn(t)
    assert orc.to_string() == peers[0].to_string()
    assert_equivalent(nat, orc)


@pytest.mark.slow
def test_native_replays_sveltecomponent():
    data = load_testing_data(trace_path("sveltecomponent"))
    nat = NativeListCRDT()
    a = nat.get_or_create_agent_id("trace")
    pos, dels, ins_lens, cps = [], [], [], []
    for txn in data.txns:
        for p in txn.patches:
            pos.append(p.pos)
            dels.append(p.del_len)
            ins_lens.append(len(p.ins_content))
            cps.extend(ord(c) for c in p.ins_content)
    nat.replay_trace(a, pos, dels, ins_lens, cps)
    assert nat.to_string() == data.end_content


@pytest.mark.slow
def test_native_replays_automerge_paper():
    data = load_testing_data(trace_path("automerge-paper"))
    nat = NativeListCRDT()
    a = nat.get_or_create_agent_id("trace")
    pos, dels, ins_lens, cps = [], [], [], []
    for txn in data.txns:
        for p in txn.patches:
            pos.append(p.pos)
            dels.append(p.del_len)
            ins_lens.append(len(p.ins_content))
            cps.extend(ord(c) for c in p.ins_content)
    nat.replay_trace(a, pos, dels, ins_lens, cps)
    assert nat.to_string() == data.end_content
    assert len(nat) == len(data.end_content)


def test_native_library_is_keyed_on_the_host_cpu(monkeypatch):
    """The library is built with -march=native: a checkout copied to
    another host must not find (and load) this host's build."""
    from text_crdt_rust_tpu.native import build as NB

    here = NB.lib_path()
    monkeypatch.setattr(NB, "_host_cpu", lambda: "some other cpu")
    assert NB.lib_path() != here
    monkeypatch.setattr(NB, "FLAGS", NB.FLAGS[:-1])
    assert NB.lib_path() != here
