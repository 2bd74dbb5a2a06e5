"""Per-layer metric readers, one module per metric, found by its name.

Each module has ``read(reading) -> float | None``. ``reading`` carries
what one ``--trace 1`` run measured:

  units        sweep units the window completed;
  jit_host_s   host seconds inside the window during which JAX traced,
               lowered or compiled (or fetched from the compile cache) a
               program: the union of its ``jax.monitoring`` duration events;
  traced       the reduction of the profiler trace of one unit, or None:
               ``whole`` (the trace holds the whole unit: the TPU dropped no
               trace buffers), ``window_s`` (the traced span on the host clock),
               ``busy_s`` (device -> seconds an operation ran),
               ``sweep_s`` (device -> seconds of the sweep's own program)
               and ``sweep_programs`` (the program names matched).

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
