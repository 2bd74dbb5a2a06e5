"""Host seconds of the program's ``sweep.build`` span in one traced unit:
policy resolution, simulator construction and ``as_jax``, before the
sweep's jit call (``experiments/runner.py``)."""
from bench import stage_trace


def read(r):
    spans = stage_trace.read_spans(r, stage_trace.BUILD_SPAN)
    return None if spans is None else sum(e - s for _, s, e in spans) / 1e9
