"""Share of the vmapped loop's lane iterations in which the lane had
already ended: 1 - sum of ``Metrics.steps`` over B x the sum over
heuristics of the slowest lane's, summed over the window's units."""
from bench import stage_trace


def read(r):
    return stage_trace.idle_lane_share(getattr(r, "outputs", None))
