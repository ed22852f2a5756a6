"""The device a run is on, the table of peaks, and the compile cache.

Peaks are published numbers, keyed by ``device_kind`` as JAX reports it; a
device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT_DIR = os.path.join(REPO, "benchmarks", "out")
# One file may grow to this and no further (the driver's TPU host caps
# file size; a write past the cap is EFBIG there).
FILE_LIMIT_BYTES = 64 * 1024 * 1024

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s,
# 16 GB of HBM per chip.
_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add it to PEAKS with its source")
    return PEAKS[device_kind]


def limit_file_size() -> None:
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    limit = min(x for x in (FILE_LIMIT_BYTES, soft, hard)
                if x != resource.RLIM_INFINITY)
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))


def place_compile_cache() -> str:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else
    the fixed ``.jax_cache/`` at the root of the checkout (the program's
    own default, so both agree). Every executable is kept."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def summary(chips: int, require_tpu: bool) -> dict:
    """``{"platform", "kind", "count"}`` of the first ``chips`` devices.
    With ``require_tpu`` anything but that many TPU chips raises."""
    import jax

    devices = jax.devices()
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": chips}
    if require_tpu and (out["platform"] != "tpu" or len(devices) < chips):
        raise RuntimeError(
            f"this cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {out['platform']} ({out['kind']!r})")
    return out


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used (0 where the
    backend reports none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
