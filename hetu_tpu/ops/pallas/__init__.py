"""Pallas TPU kernels.

One rule decides how every kernel in the package runs, and whether the
dispatching ops pick a kernel at all: compiled by Mosaic on platform
``tpu``, interpreted (tests) or replaced by the jnp reference anywhere
else.  There is no fallback from a kernel to a reference: an error
raised while tracing, lowering or compiling a kernel is the caller's
error.
"""
import jax


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"
