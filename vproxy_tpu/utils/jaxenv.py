"""JAX platform and compile-cache selection for entry points and tests.

``force_cpu(n)`` pins this process to the CPU platform with *n* virtual
host devices (tests, the ``_verify_*`` flows, the tools' ``main()``);
``cpu_subprocess_env(n)`` builds the same pin as an env dict for a child
process. Both only set ``JAX_PLATFORMS`` / ``XLA_FLAGS`` and must run
before the first device touch.

``compile_cache_dir()`` places the persistent XLA compilation cache:
wherever ``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads the
variable itself; nothing is set in code), otherwise the fixed
``<checkout>/.jax_cache``. The path is part of the cache key, so it is
never derived from a temp name, a pid or the clock.

This module imports jax only inside the functions that need it, so a
supervising parent (`python -m vproxy_tpu daemon`) can use it without ever
claiming the accelerator.
"""
from __future__ import annotations

import os
import re
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _with_host_device_flag(flags: str, n_devices: int) -> str:
    """Set (or replace a differing) host-device-count flag in *flags*."""
    pat = r"--xla_force_host_platform_device_count=\d+"
    new = f"--xla_force_host_platform_device_count={n_devices}"
    if re.search(pat, flags):
        return re.sub(pat, new, flags)
    return (flags + " " + new).strip()


def force_cpu(n_devices: int | None = None) -> None:
    """Pin this process to the CPU platform (with *n_devices* virtual
    devices). Idempotent; call before any device is touched."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        os.environ["XLA_FLAGS"] = _with_host_device_flag(
            os.environ.get("XLA_FLAGS", ""), n_devices)
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_platforms", "cpu")


def cpu_subprocess_env(n_devices: int | None = None) -> dict:
    """Env for running a child process on the CPU platform."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        env["XLA_FLAGS"] = _with_host_device_flag(
            env.get("XLA_FLAGS", ""), n_devices)
    return env


def compile_cache_dir() -> str:
    """Point JAX's persistent compilation cache at its one fixed place
    and return that directory. Call before the first compile."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env  # JAX's own handling of the variable stands
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
