"""kernel.rle_lanes_roofline_share (%): the ops.rle_lanes blocked pass's
share of its roofline.

Device time per pass: the trace's events of the kernel, matched on the
name the Pallas call is given (``rle_lanes_replay``: XLA names the
custom call's instruction after it), summed and divided by the passes
the window ran.  Each pass makes exactly one such call; a window whose
count of them differs from its passes reads nothing, as does a program
whose call has no such name.  The least time a pass can take is its HBM
bytes (``hbm_rle_lanes.rle_lanes_pass_bytes``, from the pass's shapes)
over the chip's HBM bandwidth (``peaks.json``): no integer VPU peak is
published for the chip and the kernel does no matrix work.  Nothing to
read (no trace, no kernel event) gives ``None``.
"""
import sys

from benchmark.hbm import device_peaks
from benchmark.hbm_rle_lanes import rle_lanes_pass_bytes

KERNEL = r"^%?rle_lanes_replay(\.\d+)?( |$)"


def read(rec):
    trace, shape = rec.get("trace"), rec.get("rle_lanes_shape")
    passes = rec.get("passes", 0)
    if trace is None or shape is None or passes <= 0:
        return None
    calls, seconds = trace.kernel(KERNEL)
    if calls != passes or seconds <= 0:
        print(f"[kernel.rle_lanes_roofline_share] {calls} rle_lanes_replay "
              f"calls in a window of {passes} passes: nothing read",
              file=sys.stderr)
        return None
    least = rle_lanes_pass_bytes(
        shape["steps_padded"], shape["batch"], shape["capacity"],
        shape["block_k"]) \
        / device_peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / passes)
