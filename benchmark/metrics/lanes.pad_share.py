"""lanes.pad_share (%): of the lane-steps an ``ops.rle_lanes`` pass
runs, those that are padding, 1 - real / stepped.  Real lane-steps are
the steps the lanes' own histories hold (the sum of their streams'
lengths before ``stack_ops`` padded them to the longest); stepped ones
are the pass's padded steps times its lanes.  Read from the program's
counter on the replayer (``ops.rle_lanes.LaneSteps``), which the
driver records; every pass runs the same streams.  A program without
the counter, or a pass of no steps, reads nothing."""


def read(rec):
    steps = rec.get("lane_steps")
    if not steps or steps.get("stepped", 0) <= 0:
        return None
    return 100.0 * (1.0 - steps["real"] / steps["stepped"])
