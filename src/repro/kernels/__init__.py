"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package has:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd wrapper (interpret=None: compiled on a TPU, interpreted
              elsewhere, so CPU tests validate the kernel bodies)
  ref.py    — pure-jnp oracle the tests assert against

Kernels are NOT used in the multi-pod dry-run HLO (Mosaic does not lower on
the CPU placeholder backend); ``ModelConfig.use_pallas`` switches the model
zoo onto them when running on real TPUs.
"""

import jax


def default_interpret() -> bool:
    """Interpret kernels unless computations land on a TPU: the device a
    ``jax.default_device`` scope pins, else the default backend.  So a
    CPU reference run inside a TPU process interprets, and the TPU path
    never does."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() != "tpu"
    return (dev if isinstance(dev, str) else dev.platform) != "tpu"
