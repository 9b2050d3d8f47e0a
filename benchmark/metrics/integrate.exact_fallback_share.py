"""integrate.exact_fallback_share (%): of a merge pass's remote inserts,
those whose vectorised conflict scan (``integrate_fast``) flagged its
window and ran the exact serial walk, over all remote inserts.  Read
from the program's per-lane counters (``ops.rle_mixed``, ``meta`` rows 1
and 2 of a pass), which the merge driver reads off its first pass; every
pass runs the same stream.  A program without the counters reads 0
remote inserts, and nothing is read."""


def read(rec):
    counts = rec.get("integrate")
    if not counts or counts.get("remote_inserts", 0) <= 0:
        return None
    return 100.0 * counts["exact_fallbacks"] / counts["remote_inserts"]
