"""dispatch.host_us_per_pass (us): the host's own work to dispatch one
replay pass (kernel enqueue and per-group unpack), the thread CPU time
of the program's ``rle.dispatch`` spans over their count
(``obs.trace.span_totals``).  CPU time, not the span's wall: once the
runtime's queue is full the unpack waits for the device, and that wait
is the kernel's time, not dispatch's.  Every pass of the window is one
such span and no other call makes one, so a count that differs from the
window's passes reads nothing, as does a program without the span."""
import sys


def read(rec, totals=None):
    if totals is None:
        try:
            from text_crdt_rust_tpu.obs.trace import span_totals
        except ImportError:
            return None
        totals = span_totals()
    spans, passes = totals.get("rle.dispatch"), rec.get("passes", 0)
    if spans is None or spans.get("cpu_s") is None or passes <= 0:
        return None
    if spans["count"] != passes:
        print(f"[dispatch.host_us_per_pass] {spans['count']} rle.dispatch "
              f"spans in a window of {passes} passes: nothing read",
              file=sys.stderr)
        return None
    return 1e6 * spans["cpu_s"] / passes
