"""Pallas TPU kernels, each with a jnp reference oracle (``ref.py``)
and a public wrapper (``ops.py``)."""

import jax


def resolve_impl(impl: str) -> str:
    """Kernel wrappers' ``impl``: ``auto`` takes the Pallas kernel on a
    TPU and the jnp reference elsewhere; ``pallas``, ``interpret`` (the
    kernel body without Mosaic, for tests) and ``ref`` force one."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl
