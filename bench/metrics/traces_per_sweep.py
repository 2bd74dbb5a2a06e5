"""Heuristic bodies JAX traced in one traced unit: the count of the
program's ``sweep.trace.<NAME>`` spans, which fire only while JAX traces
a heuristic's loop (``experiments/runner.py``)."""
from bench import stage_trace


def read(r):
    spans = stage_trace.read_spans(r, stage_trace.TRACE_SPAN)
    return None if spans is None else len(spans)
