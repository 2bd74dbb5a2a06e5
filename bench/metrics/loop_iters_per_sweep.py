"""Event-loop iterations the device ran per sweep unit: the sum over
heuristics of the slowest lane's ``Metrics.steps``, as a mean over the
window's units. The same on every platform for the same seed."""
from bench import stage_trace


def read(r):
    return stage_trace.loop_iters(getattr(r, "outputs", None))
