"""Bytes an ``ops.rle_lanes`` blocked pass must move through HBM, from
its shapes.

Kept with the benchmark beside ``hbm.py``, so that a PR that changes the
kernel cannot change the yardstick its roofline share is read against.
"""
from __future__ import annotations

from benchmark.hbm import I32


def rle_lanes_pass_bytes(steps_padded: int, batch: int, capacity: int,
                         block_k: int) -> int:
    """One pass over ``batch`` lanes, each with its own stream: the five
    op columns (``[steps_padded, batch]`` each) read once; the
    warm-start state (the ``ordp``/``lenp`` run planes, ``nlog`` and the
    three block tables) read once and written once; the ``ol``/``orr``
    origins per step and lane and the ``err`` flags written once.  The
    state stays in VMEM for the whole pass, so this is the least
    traffic the pass can have."""
    nbt = max(8, capacity // block_k)
    op_columns = 5 * steps_padded * batch
    state = 2 * capacity * batch + batch + 3 * nbt * batch
    origins = 2 * steps_padded * batch
    flags = 8 * batch
    return I32 * (op_columns + 2 * state + origins + flags)
