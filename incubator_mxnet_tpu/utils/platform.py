"""Process-level jax set-up for CLI entry points: where compiled
programs are cached, and the examples' CPU switch."""
import os
import warnings

__all__ = ["maybe_force_cpu", "enable_compile_cache"]

# the cache key includes the directory, so it is one fixed path in the
# checkout (listed in .gitignore) — never a temporary or per-process one
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Turn on jax's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it
    and this sets no directory; otherwise the cache lives at
    ``.jax_cache`` in the checkout.  Every program is cached, however
    quick its compile.  This is the only place that sets a cache
    directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # -1 disables the entry-size floor; 0 would mean "use the default"
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def maybe_force_cpu():
    """``MXTPU_FORCE_CPU=1`` runs an example on the host CPU with 8
    virtual devices (``JAX_PLATFORMS=cpu`` plus the device-count flag
    in one switch).  Returns True iff the pin is in effect; warns when
    it comes too late because an accelerator backend is already
    initialized."""
    flag = os.environ.get("MXTPU_FORCE_CPU", "").lower()
    if flag in ("", "0", "false", "no"):
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax
    # no public call says whether a backend is initialized already
    from jax._src import xla_bridge as _xb
    if _xb.backends_are_initialized():
        if jax.default_backend() != "cpu":
            warnings.warn(
                "MXTPU_FORCE_CPU set, but an accelerator backend "
                "already initialized — the CPU pin cannot take "
                "effect; call maybe_force_cpu() before any device "
                "op", stacklevel=2)
            return False
        return True
    jax.config.update("jax_platforms", "cpu")
    return True
