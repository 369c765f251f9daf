"""Runtime utilities: checkpointing, metrics sinks, tracing."""

import os

#: the persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path inside the checkout (the path is part of the cache key, so a
#: directory that moves never hits)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def on_tpu() -> bool:
    """The one backend rule: True on ``tpu`` (compiled Pallas kernels,
    full shapes), False on a ``cpu`` that was asked for (interpret-mode
    kernels, test shapes), an error on anything else — every site that
    used to ask "are we on the chip" in its own words calls this, so an
    unknown backend can never be a silent choice of either path.

    A ``cpu`` nobody asked for is an error too: when the TPU runtime fails
    to initialise, JAX logs it and hands back ``CpuDevice`` — the program
    would train on the CPU and exit 0. Only ``JAX_PLATFORMS=cpu`` (what
    ``jax.config.jax_platforms`` holds) makes the CPU a choice."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu":
        if not (jax.config.jax_platforms or "").startswith("cpu"):
            raise RuntimeError(
                "JAX fell back to the 'cpu' backend without being asked "
                "to (no accelerator initialised; JAX_PLATFORMS="
                f"{jax.config.jax_platforms!r}): set JAX_PLATFORMS=cpu to "
                "run on the CPU on purpose")
        return False
    raise RuntimeError(
        f"unsupported JAX backend {backend!r}: fedml_tpu runs on 'tpu' "
        "(compiled kernels) or 'cpu' (interpreted kernels, tests)")


def enable_persistent_compilation_cache() -> str:
    """Wire JAX's persistent compilation cache into this process and
    return its directory. Every CLI entrypoint calls this before its
    first compile.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: JAX reads
    the variable itself, so when it is set nothing here touches the
    directory. Unset, the cache lives at :data:`DEFAULT_COMPILE_CACHE_DIR`.
    Either way every entry is persisted, not just the slow ones — a 2 s
    compile saved is still 2 s of chip time.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
