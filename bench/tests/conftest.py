"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SEED = 2**31 + 977  # more than 32 signed bits hold, as a run's seed may


CELL = "paper.poisson_no_felare"


def tiny(name: str = CELL, n_tasks: int = 60, reps: int = 1,
         traffic: str | None = None) -> dict:
    """The cell as ``BENCHMARK.json`` has it, at a size a test can hold;
    ``traffic`` puts another mix of ``bench/traffic/`` in its place."""
    import json

    from bench import run

    c = run.load_cell(name)
    if traffic:
        c["traffic"] = json.loads(
            (ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
    c["traffic"].update(n_tasks=n_tasks, reps=reps)
    return c


def run_tiny(name: str, n_tasks: int = 60, seed: int = SEED, keep=None):
    from bench import run

    return run.run_cell(tiny(name, n_tasks), seed, 0.0, False,
                        require_tpu=False, keep=keep)

