"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships three files:
  kernel.py - pl.pallas_call body + explicit BlockSpec VMEM tiling
  ops.py    - the jit'd public wrapper (+ block-shape candidates for the
              tile-size autotuner)
  ref.py    - pure-jnp oracle used by the allclose test sweeps

Kernels target TPU: on a TPU backend they compile natively (Mosaic); on
any other backend the model's call sites run them with interpret=True
(`interpret_mode`). The dry-run lowers the jnp paths instead (DESIGN.md).
"""


def interpret_mode() -> bool:
    """Whether the model's Pallas call sites run their kernels in
    interpret mode: everywhere except on a TPU backend, where they must
    compile natively."""
    import jax
    return jax.default_backend() != "tpu"
