"""Pallas TPU kernel for the graph engine's compute hot-spot.

  spmv/        blocked-CSR semiring SpMV with frontier block skipping — the
               SEM "fetch edge chunk, combine with neighbor state" hot loop.

The package ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper) and ref.py (pure-jnp oracle); tests sweep shapes/dtypes in
interpret mode against the oracle.
"""
