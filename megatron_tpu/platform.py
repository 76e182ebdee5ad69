"""Where the program runs: CPU forcing for tests, the compile cache's
place, and what is known about the device.

On the chip JAX picks the TPU by default and fails at start-up if it
cannot; on a CPU host `JAX_PLATFORMS=cpu` selects the CPU. Neither needs
help from code — the helpers here cover what the environment cannot say.
"""

from __future__ import annotations

import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: a
#: fixed path inside the checkout — never a temporary name, a pid or a
#: time, because a directory that moves never hits
DEFAULT_COMPILE_CACHE = os.path.join(_REPO, ".jax_cache")


def force_cpu(n_devices: int = 8) -> None:
    """Select the CPU platform with >= n_devices virtual devices.

    Must run before the first jax API call that initializes a backend
    (the device count is read once, at backend init). Mutates os.environ
    (callers that must not leak the override into child processes should
    snapshot/restore around this).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    count = max(n_devices, int(m.group(1)) if m else 0)
    flag = f"--xla_force_host_platform_device_count={count}"
    if m:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache(explicit_dir: str = "") -> str:
    """Place JAX's persistent compilation cache; every entry point calls
    this before its first jit. Returns the directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set JAX already uses it and nothing
    is set here. Otherwise the cache goes to `explicit_dir` (the user's
    --compilation_cache_dir) or, by default, to `.jax_cache/` in the
    checkout. Whether the cache is on at all stays JAX's own switch
    (jax_enable_compilation_cache; the test suite turns it off)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = explicit_dir or DEFAULT_COMPILE_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    # jax initializes its cache object at most once, on the first compile:
    # a process that compiled anything before this call holds "no cache"
    # until reset
    compilation_cache.reset_cache()
    return path


def device_summary() -> dict:
    """{"platform", "kind", "count"} as JAX reports the devices — what
    every result line names and what the start-up log prints."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu() -> dict:
    """device_summary(), or RuntimeError when the backend is not a TPU:
    a measurement path that finds no chip fails, it does not continue on
    the CPU."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"this run needs a TPU; JAX reports {dev} — refusing to "
            "continue on another backend")
    return dev


# Peak dense bf16 FLOP/s by jax device_kind (public spec sheets; v5e:
# Google Cloud documentation "TPU v5e", 197 TFLOP/s). chip_smoke.py's
# device gate asks it; the benchmark keeps its own table
# (benchmark/peaks.json, ROADMAP D13). Keyed on the exact string the
# runtime reports, lower-cased: a kind that is not here is an error, never
# a default.
_PEAK_BF16 = {
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,   # v5e
    "tpu v5": 459e12,        # v5p
    "tpu v6 lite": 918e12,   # v6e
}


def peak_bf16_flops(device) -> float:
    """Peak bf16 FLOP/s for a jax device. ValueError for a device_kind that
    is not in the table."""
    kind = getattr(device, "device_kind", str(device))
    try:
        return _PEAK_BF16[kind.lower()]
    except KeyError:
        raise ValueError(
            f"no peak bf16 FLOP/s on record for device_kind {kind!r}; add "
            f"it to megatron_tpu/platform.py with its source (known: "
            f"{sorted(_PEAK_BF16)})") from None
