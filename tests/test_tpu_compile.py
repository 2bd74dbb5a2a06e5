"""The main path's Pallas kernels compile for a TPU v5e at real shapes.

Each test lowers one kernel with ``interpret=False`` against a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what interpret mode accepts: unaligned slices,
unsupported casts or reductions, too much fast memory. Nothing runs, so
these say nothing about results or times; ``chip_smoke.py`` does that on
the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import policy
from repro.kernels.map_fused import kernel as fused
from repro.kernels.phase1_map.kernel import phase1_map_padded

F32, I32 = jnp.float32, jnp.int32
SP = 8  # EET type rows, padded to the f32 sublane count


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args, kernel: str):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not interpreted
    # under its stable name, which a profile of the chip shows
    assert f"%{kernel}" in text
    return text


def _map_decide_args(shape, n, mp, batch=()):
    return (shape(batch, F32), shape(batch + (mp,), F32),
            shape(batch + (mp,), F32), shape(batch + (mp,), I32),
            shape(batch + (SP, mp), F32), shape(batch + (n,), F32),
            shape(batch + (n,), I32), shape(batch + (n,), I32),
            shape(batch + (n,), I32))


def _kinds(name):
    d = policy.describe(name)
    return dict(nominator=d.nominator, phase2_key=d.phase2_key,
                drop_rule=d.drop_rule)


@pytest.mark.parametrize("n,mp", [(2048, 128), (10240, 512)])
@pytest.mark.parametrize("heuristic", ["ELARE", "FELARE", "RANDOM"])
def test_map_decide_compiles(shape, heuristic, n, mp):
    kinds = _kinds(heuristic)
    _compile(lambda *a: fused.map_decide_padded(
        *a, n_machines=mp, interpret=False, **kinds),
        *_map_decide_args(shape, n, mp), kernel="map_decide")


def test_map_decide_vmapped_compiles(shape):
    """The engine vmaps the simulator, so each call gains a batch axis."""
    body = jax.vmap(lambda *a: fused.map_decide_padded(
        *a, n_machines=4, interpret=False, **_kinds("FELARE")))
    _compile(body, *_map_decide_args(shape, 2048, 128, batch=(8,)),
             kernel="map_decide")


def test_evict_stats_compiles(shape):
    n, mp = 2048, 128
    _compile(lambda *a: fused.evict_stats_padded(*a, interpret=False),
             shape((mp,), F32), shape((mp,), I32), shape((SP, mp), F32),
             shape((n,), F32), shape((n,), I32), shape((n,), I32),
             kernel="evict_stats")


def test_balance_scan_compiles(shape):
    n, fp = 2048, 128
    _compile(lambda *a: fused.balance_scan_padded(
        *a, n_tasks=2000, interpret=False),
        shape((fp,), I32), shape((n,), I32), shape((n,), I32),
        shape((n,), I32), kernel="balance_scan")


def test_phase1_map_compiles(shape):
    n, mp = 2048, 128
    _compile(lambda *a: phase1_map_padded(*a, interpret=False),
             shape((mp,), F32), shape((mp,), F32), shape((mp,), I32),
             shape((n, mp), F32), shape((n,), F32), shape((n,), I32),
             kernel="phase1_map")
