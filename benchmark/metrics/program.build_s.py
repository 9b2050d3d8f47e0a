"""program.build_s (s): the replayer's warm build, the sum of the
program's ``rle.stage`` (op columns padded and put on the device) and
``rle.build`` (the first call: trace, lower, compile-cache load, first
enqueue) span totals (``obs.trace.span_totals``: the whole process,
which is one run).  A build that compiled anything from scratch (a
checkout's first run, the persistent compile cache still empty) reads
nothing, with a line on stderr, as does a program without the spans."""
import sys

KINDS = ("rle.stage", "rle.build")


def read(rec, totals=None):
    if totals is None:
        try:
            from text_crdt_rust_tpu.obs.trace import span_totals
        except ImportError:
            return None
        totals = span_totals()
    if not all(k in totals for k in KINDS):
        return None
    compiles = sum(totals[k].get("compiles", 0) for k in KINDS)
    if compiles:
        print(f"[program.build_s] a cold build: {compiles} executable(s) "
              f"compiled from scratch: nothing read", file=sys.stderr)
        return None
    return sum(totals[k]["total_s"] for k in KINDS)
