"""FELARE reproduction package.

JAX is optional at import time: the static analyzer's AST layer
(``repro.analysis``, Layer 1) runs on the JAX-less CI lint runner, so
``import repro`` must not import JAX — only the subpackages that actually
trace (core, scenarios, experiments, ...) require it.
"""
