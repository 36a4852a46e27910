"""Pallas kernels (flash, kvq, pack, ssd), each with a jnp ``ref`` oracle.

Backends: ``pallas`` (compiled Mosaic, TPU only), ``interpret`` (the
Pallas interpreter, for CPU tests) and ``ref`` (the jnp oracle).
"""


def platform_backend() -> str:
    """The kernel backend this process's platform runs: the compiled
    Pallas kernels on a TPU, the jnp ``ref`` oracles elsewhere.
    ``interpret`` is never chosen here; it is a CPU test mode."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "ref"
