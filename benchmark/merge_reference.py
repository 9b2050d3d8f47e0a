"""The B2.4 merge deployment, generated and checked by the benchmark itself.

- ``b24_history``: one client's crdt-benchmarks B2.4 ("Concurrently insert
  & delete") history, typed into the benchmark's own plain replica
  (``ref/oracle.py``) from a seed: each round inserts a word of 3-9
  letters at a uniform position, then either a second word of 2-10
  letters at the same index (p = 0.5) or deletes 1 to min(9, rest)
  chars there.  Every edit is a transaction of its own.
- ``b24_updates``: both clients' histories from one seed, each exported
  as the update a client sends on reconnect (``ref/sync.py``), and the
  count of edit operations drawn.
- ``server_replica``: the sync server's replica, the updates applied as
  remote txns in the order given.
- ``signed_orders``: a replica's body as ±(order+1) per character in
  document order (negative for a tombstone), the form the program's run
  state decodes to (``reference.decode_rle_lane``).
- ``encode_signed_lane``: the inverse, ``ops.rle_mixed``'s run layout
  written by the benchmark's own code (the control puts a reference's
  state in the program's place with it).

Nothing here imports the program.
"""
from __future__ import annotations

import random
import string
from typing import List, Sequence, Tuple

import numpy as np

from .ref.common import RemoteTxn
from .ref.oracle import ListCRDT
from .ref.sync import export_txns_since


def _word(rng: random.Random, lo: int, hi: int) -> str:
    return "".join(rng.choice(string.ascii_lowercase)
                   for _ in range(rng.randint(lo, hi)))


def b24_history(agent: str, rounds: int, rng: random.Random) -> ListCRDT:
    """The client's replica after ``rounds`` B2.4 rounds (two edits
    each)."""
    doc = ListCRDT()
    aid = doc.get_or_create_agent_id(agent)
    n = 0
    for _ in range(rounds):
        index = rng.randint(0, n)
        word = _word(rng, 3, 9)
        doc.local_insert(aid, index, word)
        n += len(word)
        if n == index or rng.random() < 0.5:
            word = _word(rng, 2, 10)
            doc.local_insert(aid, index, word)
            n += len(word)
        else:
            count = rng.randint(1, min(9, n - index))
            doc.local_delete(aid, index, count)
            n -= count
    return doc


def b24_updates(rounds: int, clients: Sequence[str], seed: int
                ) -> Tuple[List[List[RemoteTxn]], int]:
    """([each client's update], edit ops of all clients): the clients
    edit concurrently from an empty document, none seeing another."""
    rng = random.Random(seed)
    updates = [export_txns_since(b24_history(name, rounds, rng), 0)
               for name in clients]
    return updates, 2 * rounds * len(clients)


def server_replica(updates: Sequence[Sequence[RemoteTxn]]) -> ListCRDT:
    doc = ListCRDT()
    for txns in updates:
        for t in txns:
            doc.apply_remote_txn(t)
    return doc


def signed_orders(doc: ListCRDT) -> np.ndarray:
    o = doc.order[:doc.n].astype(np.int64) + 1
    return np.where(doc.deleted[:doc.n], -o, o)


def encode_signed_lane(signed: np.ndarray, capacity: int, block_k: int,
                       nblp: int) -> Tuple[np.ndarray, ...]:
    """±(order+1) per character -> (ordp, lenp, blkord, rows, nlog):
    maximal runs of same-sign consecutive orders, ``block_k - 1`` to a
    block in logical order."""
    runs: List[List[int]] = []
    for s in signed.tolist():
        if runs and (s > 0) == (runs[-1][0] > 0) \
                and abs(runs[-1][0]) + runs[-1][1] == abs(s):
            runs[-1][1] += 1
        else:
            runs.append([s, 1])
    per = block_k - 1
    nlog = max(1, -(-len(runs) // per))
    if nlog > capacity // block_k:
        raise ValueError(f"{len(runs)} runs exceed {capacity} rows")
    ordp = np.zeros(capacity, np.int32)
    lenp = np.zeros(capacity, np.int32)
    blkord = np.zeros(nblp, np.int32)
    rows = np.zeros(nblp, np.int32)
    for slot in range(nlog):
        chunk = runs[slot * per:(slot + 1) * per]
        blkord[slot] = slot
        rows[slot] = len(chunk)
        for r, (start, n) in enumerate(chunk):
            ordp[slot * block_k + r] = start
            lenp[slot * block_k + r] = n
    return ordp, lenp, blkord, rows, nlog
