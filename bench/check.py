"""The comparison that decides ``correct``.

Every lane (one trace under one heuristic) that the window simulated is
checked by the configuration's guarantees, and a sample of them, drawn
from the seed, against the plain reference (``bench/reference.py``):

  lanes_bad       lanes where completed + missed + cancelled != arrived for
                  some task type, where arrived per type differs from the
                  lane's own trace, or where the makespan lies outside [last
                  arrival, last deadline] of the lane's trace (a lane lost,
                  cut short or swapped); exact, limit 0;
  counter_diff    sum over the sampled lanes of |program - reference| of the
                  per-type completed/missed/cancelled/arrived counters;
                  exact, limit 0;
  energy_rel_err  largest relative gap, over the sampled lanes, of dynamic,
                  wasted and idle energy and makespan (the program sums in
                  float32, the reference in float64); limit from the
                  traffic file, set from measured readings (PERF.md).

The sample holds ``sample_lanes`` lanes spread evenly over the heuristics;
each heuristic's first lane is drawn from the highest rate, where most
tasks contend. The control is the reference with the same rules computed
in bfloat16; ``control`` puts it in the program's place on the sampled
lanes.
"""
from __future__ import annotations

import numpy as np

from bench import reference

COUNTERS = ("completed_by_type", "missed_by_type", "cancelled_by_type",
            "arrived_by_type")
REALS = ("energy_dynamic", "energy_wasted", "energy_idle", "makespan")


def lanes_bad(traces: dict, metrics: dict, n_types: int) -> int:
    """Lanes of one unit that break the fleet's guarantees or do not fit
    their own trace.

    ``traces``: (B, ...) arrays; ``metrics``: (H, B, ...) arrays."""
    want = np.stack([np.bincount(t, minlength=n_types)
                     for t in traces["task_type"]])           # (B, S)
    ended = (metrics["completed_by_type"] + metrics["missed_by_type"]
             + metrics["cancelled_by_type"])                  # (H, B, S)
    arrived = metrics["arrived_by_type"]
    bad = ((ended != arrived) | (arrived != want[None])).any(-1)
    # the last event falls between the last arrival and the last deadline
    span = metrics["makespan"]                                # (H, B)
    bad |= ((span < traces["arrival"].max(-1)[None])
            | (span > traces["deadline"].max(-1)[None]))
    return int(bad.sum())


def draw_sample(seed: int, n_units: int, heuristics, n_rates: int,
                reps: int, n_lanes: int) -> list:
    """[(unit, heuristic index, batch index)] drawn from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x5EED])
    H = len(heuristics)
    out = []
    for i in range(max(n_lanes, H)):
        h = i % H
        top = i < H  # first lane of each heuristic: the highest rate
        r = n_rates - 1 if top else int(rng.integers(n_rates))
        out.append((int(rng.integers(n_units)), h,
                    r * reps + int(rng.integers(reps))))
    return out


def _lane(tree: dict, *idx):
    return {k: v[idx] for k, v in tree.items()}


def compare(units, fleet: dict, traffic: dict, seed: int,
            control: bool = False) -> dict:
    """Numbers compared, each ``{"value": v, "limit": l}``, and the counts.

    ``units``: [(traces, metrics)] of the window, host numpy dicts. With
    ``control``, the sampled lanes' results come from the reference in
    bfloat16 instead of from the program."""
    heuristics = traffic["heuristics"]
    limits = traffic["check"]["limits"]
    n_types = len(fleet["eet"])
    bad = sum(lanes_bad(t, m, n_types) for t, m in units)
    sample = draw_sample(seed, len(units), heuristics, len(traffic["rates"]),
                         int(traffic["reps"]),
                         int(traffic["check"]["sample_lanes"]))
    diff, worst, failed_sample = 0, 0.0, 0
    for u, h, b in sample:
        traces, metrics = units[u]
        lane = _lane(traces, b)
        ref = reference.simulate(lane, fleet, heuristics[h])
        got = (reference.simulate(lane, fleet, heuristics[h], "bfloat16")
               if control else _lane(metrics, h, b))
        d = sum(int(np.abs(np.asarray(got[c]) - ref[c]).sum())
                for c in COUNTERS)
        e = max(abs(float(got[r]) - ref[r]) / max(abs(ref[r]), 1e-30)
                if ref[r] or got[r] else 0.0 for r in REALS)
        diff += d
        worst = max(worst, e)
        failed_sample += bool(d or e > limits["energy_rel_err"])
    numbers = {
        "lanes_bad": {"value": bad, "limit": 0},
        "counter_diff": {"value": diff, "limit": 0},
        "energy_rel_err": {"value": worst,
                           "limit": limits["energy_rel_err"]},
    }
    correct = bool(units) and all(v["value"] <= v["limit"]
                                  for v in numbers.values())
    return dict(numbers=numbers, correct=correct, lanes_bad=bad,
                failed_sample=failed_sample, sampled=len(sample))
