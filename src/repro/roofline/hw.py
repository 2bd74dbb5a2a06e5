"""Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU v5e").

These describe a v5e, not whatever device the process runs on. Code that
divides a measured rate by them must first see ``"v5e"`` in
``jax.devices()[0].device_kind``. ``roofline/analysis.py`` uses them as
the target of its model of a described topology.
"""

PEAK_FLOPS_BF16 = 197e12     # per chip, bf16
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per-chip usable, one axis)
HBM_BYTES = 16 * 2**30       # 16 GiB per chip
CHIPS_PER_POD = 256
