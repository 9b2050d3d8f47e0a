"""Bytes an ``ops.rle_mixed`` pass must move through HBM, from its shapes.

Kept with the benchmark beside ``hbm.py``, so that a PR that changes the
kernel cannot change the yardstick its roofline share is read against.
"""
from __future__ import annotations

from benchmark.hbm import I32

TABLE_LANES = 128  # orders per row of the by-order tables


def table_rows(orders: int, lmax: int) -> int:
    """Rows of each by-order table for a stream of ``orders`` orders
    (one spare tail row, rounded up to a sublane tile of 8)."""
    ocap = max(orders + lmax, TABLE_LANES)
    rows = -(-ocap // TABLE_LANES) + 1
    return -(-rows // 8) * 8


def rle_mixed_pass_bytes(steps_padded: int, batch: int, capacity: int,
                         block_k: int, orders: int, lmax: int) -> int:
    """One pass over ``batch`` lanes: the nine op columns and the three
    by-order tables (origin-left, origin-right, rank) read once; every
    output written once (``ol``/``orr`` per step and lane, the
    ``ordp``/``lenp`` run planes, the block tables, ``meta`` and
    ``err``).  The run planes and their aux planes stay in VMEM for the
    whole pass, so the run planes are written back once and never read:
    this is the least traffic the pass can have."""
    nblp = max(8, capacity // block_k)
    op_columns = 9 * steps_padded
    tables = 3 * table_rows(orders, lmax) * TABLE_LANES
    origins = 2 * steps_padded * batch
    state = 2 * capacity * batch
    blocks = 2 * nblp * batch
    flags = 2 * 8 * batch
    return I32 * (op_columns + tables + origins + state + blocks + flags)
