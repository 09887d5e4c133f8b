"""Small runtime-config helpers shared by the CLI drivers."""

from __future__ import annotations

import os


def resolve_dtype(name: str):
    """Map a ``--dtype`` flag to a jnp dtype, enabling x64 first when needed
    (jax truncates f64 arrays silently otherwise)."""
    import jax
    import jax.numpy as jnp

    if name == "float64":
        jax.config.update("jax_enable_x64", True)
        return jnp.float64
    return jnp.float32


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``.jax_cache`` under
    the checkout: a fixed path, because the path is part of the cache key
    and a directory that moves never hits. Call at the top of a driver's
    ``main``, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def is_device_loss(exc: BaseException) -> bool:
    """True when an exception means the accelerator backend died under us
    (TPU worker crash). A dead backend cannot be reinitialized
    in-process, so every driver converts this into an exit-75
    process-boundary retry. One
    predicate, shared by all drivers — refine detection here only.

    A coordinated abort (``resilience.PeerFailure``) counts when ANY
    process of the job reported device loss: every process must take the
    resume-marker exit path together, not only the one whose device died."""
    import jax

    from photon_ml_tpu.parallel.resilience import PeerFailure

    if isinstance(exc, PeerFailure):
        return exc.device_loss or (exc.__cause__ is not None
                                   and is_device_loss(exc.__cause__))
    return (isinstance(exc, jax.errors.JaxRuntimeError)
            and "UNAVAILABLE" in str(exc))
