"""What JAX found, checked against what the cell asks for. Called only in
the child that holds the chip."""

from __future__ import annotations


class WrongDevice(RuntimeError):
    pass


def check_devices(chips: int, rehearse: bool) -> dict:
    """{"platform", "kind", "count"} as JAX reports them. Without
    --rehearse anything but `chips` TPU devices is an error: a device
    metric is never taken on another backend."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if not rehearse and (found["platform"] != "tpu"
                         or found["count"] != chips):
        raise WrongDevice(f"the cell asks for {chips} TPU chip(s); JAX "
                          f"reports {found}")
    return found


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    keeps no such statistic, as the CPU's does not)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
