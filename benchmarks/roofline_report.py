"""FLOP and byte model of the fused map-decision kernel."""
from __future__ import annotations


def map_stage(full=False):
    """Trip-exact FLOP/byte model of the fused map-decision kernel.

    Traces :func:`repro.kernels.map_fused.map_decide` (the single-pass
    Pallas decision kernel) through :func:`repro.roofline.jaxpr_cost` at
    representative (N tasks x M machines) grid shapes — the shared
    ``jaxpr_walk`` visitor descends into the ``pallas_call`` kernel body
    with the grid size as the trip multiplier, so the numbers cover the
    whole tiled sweep, not one tile. The derived arithmetic intensity
    (flops/byte) is what justifies the kernel's VMEM-residency claim:
    the EET grid is read once per decision, everything else is O(N + M).
    """
    import jax.numpy as jnp

    from repro.kernels import map_fused
    from repro.roofline.jaxpr_cost import jaxpr_cost

    shapes = [(100, 8), (1000, 64)] + ([(10000, 512)] if full else [])
    n_types = 4
    rows = []
    for n, m in shapes:
        cost = jaxpr_cost(
            map_fused.map_decide,
            jnp.float32(0.0),                      # now
            jnp.zeros((m,), jnp.float32),          # start
            jnp.ones((m,), jnp.float32),           # p_dyn
            jnp.ones((m,), bool),                  # qfree
            jnp.ones((n_types, m), jnp.float32),   # eet
            jnp.ones((n,), jnp.float32),           # deadline
            jnp.ones((n,), bool),                  # pending
            jnp.zeros((n,), jnp.int32),            # task_type
            jnp.zeros((n,), bool),                 # suffered_task
            nominator="min_energy_feasible", phase2_key="urgency",
            drop_rule="stale_hopeless", interpret=True,
        )
        rows.append({
            "n_tasks": n, "n_machines": m,
            "flops": cost["flops"], "bytes": cost["bytes"],
            "matmul_flops": cost["matmul_flops"],
            "ai_flops_per_byte": round(cost["flops"] / max(cost["bytes"], 1),
                                       3),
        })
    derived = {
        "fig": "map_stage_roofline", "shapes": len(rows),
        "pass": all(r["flops"] > 0 and r["bytes"] > 0 for r in rows),
    }
    return rows, derived
