"""The device a run is on: found, checked, described, read."""

import sys


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require(chips, rehearse_cpu):
    """The devices this run uses. On the chip path anything but ``chips``
    TPU devices or more is an error; the rehearsal wants the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if rehearse_cpu else "tpu"
    if platform != want:
        raise NoChip(
            f"the platform is {platform!r}, not {want!r}: nothing was run"
        )
    if not rehearse_cpu and len(devices) < chips:
        raise NoChip(
            f"the cell asks for {chips} chip(s), JAX sees {len(devices)}"
        )
    return devices


def describe(devices, chips):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(devices, chips):
    """``peak_bytes_in_use`` of the fullest chip used (0 where the
    backend keeps no allocator statistics, as XLA:CPU)."""
    peak = 0
    for d in devices[:chips]:
        try:
            stats = d.memory_stats() or {}
        except Exception as e:  # a backend without allocator statistics
            print(f"perfbench: no memory stats on {d}: {e}", file=sys.stderr)
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
