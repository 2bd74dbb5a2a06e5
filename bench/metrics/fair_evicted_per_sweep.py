"""Queued tasks evicted for a suffered task per sweep unit: the sum over
heuristics and lanes of ``Metrics.fair_evicted``, as a mean over the
window's units. The same on every platform for the same seed; None where
the program does not return the counter."""


def read(r):
    outputs = getattr(r, "outputs", None)
    if not outputs or "fair_evicted" not in outputs[0][1]:
        return None
    return sum(int(m["fair_evicted"].sum()) for _, m in outputs) / len(outputs)
