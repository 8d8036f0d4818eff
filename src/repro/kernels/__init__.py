"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel subpackage has kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with estimator-guided configuration selection) and ref.py (pure-jnp oracle).
entry.py holds what the estimator-picked entry points share: the span around each
call and the timed pick.
"""
