"""Share of one traced unit's span in which no operation ran on the
device, averaged over the devices used. Read only from a trace that
holds the whole unit: a part would over-weight the host work at its
start."""


def read(r):
    if r.traced is None or not r.traced["whole"] or not r.traced["busy_s"]:
        return None
    busy = r.traced["busy_s"].values()
    return 1.0 - sum(busy) / len(busy) / r.traced["window_s"]
