import os
import sys

#: the checkout root (the directory holding ``ra_tpu/``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory that
    outlives the process, and return that directory.  Called by the
    on-chip entry points (``chip_smoke.py``, ``benchmarks/run.py``)
    before their first jit; nothing calls it at import time.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this sets nothing.  Otherwise the cache lives at the FIXED path
    ``<checkout>/.jax_cache`` (git-ignored): the directory is part of
    the cache key, so a temporary or per-process name would never
    hit."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def host_envelope() -> dict:
    """Host resource envelope for bench/soak JSON tails (ISSUE 13):
    the fd cap (the wire ladder's 20k-rlimit ceiling) and the core
    count (the 1-core partition tax) both surfaced as unexplained
    cross-host drift in round captures — every capture carries them
    so drift is attributable.  ONE implementation: bench._host_meta
    and the soak tails all merge this dict."""
    env: dict = {"cpu_count": os.cpu_count()}
    try:
        import resource
        env["rlimit_nofile"] = \
            resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    except Exception:  # noqa: BLE001 — optional on exotic platforms
        pass
    # jax/jaxlib versions + backend platform (ISSUE 16): compile-time
    # and device-memory numbers are meaningless across version drift.
    # Stamped only in a process that ALREADY runs on JAX: asking for
    # the backend initialises it, and an initialised backend holds the
    # chip — the bench parent must stay off it so its children can
    # have it (one process per chip).
    jax = sys.modules.get("jax")
    if jax is not None:
        import jaxlib
        env["jax_version"] = jax.__version__
        env["jaxlib_version"] = jaxlib.__version__
        env["jax_backend"] = jax.default_backend()
    return env
