"""Pallas TPU kernels for the compute hot spots.

Each kernel directory holds:
  kernel.py -- pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- jit'd public wrapper (padding, block choice)
  ref.py    -- pure-jnp oracle used by the allclose test sweeps

The kernels always lower for the TPU.  Off the chip they run only where a
caller asks for Pallas's TPU interpreter
(``jax.experimental.pallas.tpu.force_tpu_interpret_mode``), as the CPU
tests do; a plain CPU call fails instead of silently interpreting.
"""
