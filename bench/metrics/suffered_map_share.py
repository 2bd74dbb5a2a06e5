"""Share of a fairness policy's mapping events at which Eq. 3 found a
suffered task type: the sum of ``Metrics.suffered_maps`` over the sum of
``Metrics.steps`` (one map stage per loop iteration), over the lanes of
the heuristics whose count is not 0, in the window's units. A heuristic
without fairness reads a constant 0, so its lanes drop out. None where
the program does not return the counter or no lane counted one."""


def read(r):
    outputs = getattr(r, "outputs", None)
    if not outputs or "suffered_maps" not in outputs[0][1]:
        return None
    hit = ran = 0
    for _, m in outputs:
        fair = m["suffered_maps"].any(-1)  # (H,) heuristics that counted
        hit += int(m["suffered_maps"][fair].sum())
        ran += int(m["steps"][fair].sum())
    return hit / ran if ran else None
