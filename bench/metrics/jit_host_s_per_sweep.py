"""Host seconds per sweep unit spent tracing, lowering and compiling (or
fetching from the compile cache) the sweep's program: the sweep runner's
cost that the device waits through (``experiments/runner.py``)."""


def read(r):
    return r.jit_host_s / r.units if r.units else None
