"""Device seconds of the ``admit`` stage (``engine.admit``: arrivals move
into the pending queue), in one traced unit: the self time of the leaf
ops under that scope in the sweep program (``bench/stage_trace.py``)."""
from bench import stage_trace


def read(r):
    return stage_trace.read_stage(r, "admit")
