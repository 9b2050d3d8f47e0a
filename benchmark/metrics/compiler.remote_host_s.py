"""compiler.remote_host_s (s): the remote path's host compiler, part of
set-up: the sum of the program's ``compiler.remote``
(``ops.batch.compile_remote_txns``) and ``compiler.prefill`` (the
by-order logs prefilled and packed into the kernel's tables) span
totals (``obs.trace.span_totals``: the whole process, which is one
run).  A program without them reads nothing."""

KINDS = ("compiler.remote", "compiler.prefill")


def read(rec, totals=None):
    if totals is None:
        try:
            from text_crdt_rust_tpu.obs.trace import span_totals
        except ImportError:
            return None
        totals = span_totals()
    if not all(k in totals for k in KINDS):
        return None
    return sum(totals[k]["total_s"] for k in KINDS)
