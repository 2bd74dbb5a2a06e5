"""Device seconds of the ``start`` stage (``engine.start``: idle
machines pop their queue heads), in one traced unit: the self time of the
leaf ops under that scope in the sweep program (``bench/stage_trace.py``)."""
from bench import stage_trace


def read(r):
    return stage_trace.read_stage(r, "start")
