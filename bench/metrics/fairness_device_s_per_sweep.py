"""Device seconds of the fairness step (``engine.fairness``, inside
``engine.map``: the Eq. 3 monitor, the eviction plan and the suffered-first
Phase II of a fairness policy), in one traced unit: the self time of the
leaf ops under that scope in the sweep program (``bench/stage_trace.py``).
None where no op carries the scope: a program without it, or a traffic
mix without a fairness policy."""
from bench import stage_trace


def read(r):
    s = stage_trace.read_stages(r)
    return None if s is None else s.get("fairness")
