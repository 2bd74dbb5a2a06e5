"""Device seconds of the ``finalize`` stage (``engine.finalize``:
machines whose running task ended are freed and the task counted), in one
traced unit: the self time of the leaf ops under that scope in the sweep
program (``bench/stage_trace.py``)."""
from bench import stage_trace


def read(r):
    return stage_trace.read_stage(r, "finalize")
