"""Jitted kernels of the combining runtime.

  vector_rounds — the VectorApply round bodies: one compiled kernel per
                  (kind, op) runs a combining round's announced batch
  scan_replay   — the periodic modeled-replay engine behind
                  ``benchmarks/modeled.py``

The package imports nothing itself: ``from repro.kernels import
vector_rounds`` loads only JAX and that module.
"""
