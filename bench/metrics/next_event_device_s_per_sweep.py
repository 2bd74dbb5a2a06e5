"""Device seconds of the event loop's next-event computation
(``engine.next_event``: the earliest arrival, completion or pending
deadline; it runs in the loop's ``cond`` and ``body``), in one traced
unit: the self time of the leaf ops under that scope in the sweep program
(``bench/stage_trace.py``)."""
from bench import stage_trace


def read(r):
    return stage_trace.read_stage(r, "next_event")
