"""Process-level JAX set-up shared by every entry point that reaches a jit.

Two facts an operator must be able to read off any run — where compiled
executables are kept, and which device the process is actually on — are
decided here and nowhere else.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# A cache directory that moves between runs never hits, so there is one
# fixed, git-ignored place inside the checkout unless the environment
# names another.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Decide where XLA's persistent compilation cache lives; returns the
    directory. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and
    no directory is configured in code. Unset: :data:`DEFAULT_CACHE_DIR`.
    Call before the first compile."""
    import jax

    # Keep every executable, also one that compiled in under JAX's
    # default one-second floor: the serving manifest books a hit for any
    # dispatch key a sibling warmed, so the executable must be there.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_summary() -> dict:
    """What JAX reports it is running on (initialises the backend)."""
    import jax

    devices = jax.devices()
    return {"jax": jax.__version__, "platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices)}


def device_line() -> str:
    """One log line naming the device, printed by the entry points so no
    run can leave its platform to be guessed."""
    d = device_summary()
    return (f"device: jax={d['jax']} platform={d['platform']} "
            f"device_kind={d['device_kind']!r} count={d['count']}")


def not_tpu() -> str | None:
    """Says so when the backend is not a TPU (None when it is): what the
    kernel-support predicates ask first. A backend that fails to
    initialise raises here — it is not read as "no TPU"."""
    import jax

    platform = jax.devices()[0].platform
    return None if platform == "tpu" else (
        f"the backend is {platform!r}, not tpu")


def require_tpu() -> dict:
    """:func:`device_summary`, or RuntimeError when JAX found no TPU — for
    measurement paths, which never substitute the CPU."""
    d = device_summary()
    if d["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: jax reports platform={d['platform']!r} "
            f"device_kind={d['device_kind']!r}; a chip run does not fall "
            "back to another backend")
    return d
