"""Device seconds of the sweep's own XLA program (the event loop, one
vmapped ``while_loop`` per heuristic) in one traced unit; on several
devices, the longest, since the unit ends when its last device does.
Read only from a trace that holds the whole unit."""


def read(r):
    if r.traced is None or not r.traced["whole"]:
        return None
    if not r.traced["sweep_programs"]:
        return None
    return max(r.traced["sweep_s"].values())
