"""Pallas kernels for the task bodies and the model stack.

Each kernel package holds the kernel, a public ``ops`` wrapper and a jnp
``ref`` oracle."""

import jax


def interpret_mode() -> bool:
    """Whether a kernel that must always run as Pallas takes interpret
    mode: compiled by Mosaic on the TPU, interpreted on the CPU (tests),
    and refused on any other backend rather than silently interpreted on
    an accelerator."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas task bodies run compiled on the TPU or interpreted on the "
        f"CPU; backend {backend!r} is neither")
