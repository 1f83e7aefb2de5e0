"""Pallas TPU kernels of the hot path, each with ``kernel.py`` (the
``pallas_call``), ``ops.py`` (the jitted wrapper) and ``ref.py`` (the
``jax.numpy`` oracle the tests compare against)."""

import jax


def interpret_default() -> bool:
    """Run kernels in interpret mode everywhere but on a TPU backend: the
    CPU tests execute the same kernel code through the Pallas interpreter."""
    return jax.default_backend() != "tpu"
