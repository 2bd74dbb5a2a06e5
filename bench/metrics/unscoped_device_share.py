"""Share of the sweep program's leaf-op device time in one traced unit
that lies under no ``engine.*`` stage scope: the loop's own carry
selects under ``vmap``, the per-heuristic stacking and the like
(``bench/stage_trace.py``)."""
from bench import stage_trace


def read(r):
    s = stage_trace.read_stages(r)
    if s is None:
        return None
    return s.get(None, 0.0) / sum(s.values())
