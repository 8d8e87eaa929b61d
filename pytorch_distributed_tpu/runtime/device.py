"""Device discovery.

TPU-native replacement for the reference recipes' ``model.cuda()`` /
``.to(rank)`` device placement (BASELINE.json:5): under single-controller
SPMD there is no per-rank device object to move tensors to — placement is a
property of an array's sharding. This module only answers "what hardware am I
driving", which the mesh layer turns into a ``jax.sharding.Mesh``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax


def platform() -> str:
    """Platform string of the default backend: ``tpu`` | ``cpu`` | ``gpu``."""
    return jax.devices()[0].platform


def is_tpu() -> bool:
    return platform() == "tpu"


def require_tpu_or_requested_cpu() -> str:
    """The platform, provided it is the one the caller meant: a TPU, or
    the CPU when the caller pinned JAX to it (``JAX_PLATFORMS=cpu`` or
    ``jax.config.update("jax_platforms", "cpu")``, as the tests do).
    JAX itself falls back to the host when it finds no accelerator; a
    program that then carries on measures or trains on the wrong device
    without saying so, hence an error here."""
    p = platform()
    if p == "tpu":
        return p
    if p == "cpu" and (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return p
    raise RuntimeError(
        f"JAX found no TPU (platform {p!r}) and the CPU was not asked "
        f"for: set JAX_PLATFORMS=cpu to run on the host on purpose"
    )


def device_count() -> int:
    """Total number of addressable devices across all hosts."""
    return jax.device_count()


def local_device_count() -> int:
    """Devices attached to this host (== device_count on single host)."""
    return jax.local_device_count()


def process_index() -> int:
    """Index of this controller process (0 on single host)."""
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


@functools.lru_cache(maxsize=None)
def device_kind() -> str:
    """Hardware name, e.g. ``TPU v5 lite`` — useful for logging/benchmarks."""
    return jax.devices()[0].device_kind


#: advertised peak bf16 matmul throughput per chip (FLOP/s) — the MFU
#: denominator. Sources: public TPU spec sheets.
_PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops() -> float | None:
    """Peak bf16 FLOP/s of this chip. None on the CPU only (nobody quotes
    a utilisation for the host); an accelerator missing from the table is
    an error — a silently dropped MFU reads as "not asked for"."""
    kind = device_kind()
    for name, flops in _PEAK_BF16_FLOPS.items():
        if kind.startswith(name):
            return flops
    if platform() == "cpu":
        return None
    raise ValueError(
        f"no peak FLOP/s for device_kind {kind!r}: add it, with its "
        f"source, to runtime/device.py _PEAK_BF16_FLOPS"
    )


def compiled_flops(compiled) -> float | None:
    """FLOPs per execution from a lowered+compiled computation's XLA cost
    analysis; None when the backend doesn't expose it."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca["flops"]) if ca and "flops" in ca else None
    except Exception:
        return None


def enable_compilation_cache(*, best_effort: bool = False) -> str:
    """Persistent XLA executable cache — compile once, reuse across runs.

    The reference relies on CUDA's kernel caches for fast restarts; the
    XLA analogue is the persistent compilation cache. Where it lives is
    decided in this one function and nowhere else:

    * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
      function sets NO directory in code — whoever runs the program (a
      chip machine that keeps its cache between calls, a CI runner)
      places the cache from outside;
    * otherwise ``<checkout>/.jax_cache/isa-<hash>``: one fixed path per
      host, never built from a temporary name, a pid or the time — the
      path is part of the cache key's inputs, so a directory that moves
      never hits. The ``isa-`` subdir is a hash of /proc/cpuinfo's
      feature flags: JAX's key does not cover the host ISA, and XLA:CPU
      entries compiled on a wider-featured host load elsewhere with
      pages of "could lead to ... SIGILL" warnings or an actual SIGILL;
      a container that comes back on another CPU model starts a fresh
      cache instead (the provenance rule the native .so builds enforce
      through their flags sidecar, utils/native_build.py).

    Either way every executable is kept whatever its size. Returns the
    directory in use.

    ``best_effort`` swallows ANY failure (an unwritable checkout, a
    renamed jax config key) and returns "" — for tests/conftest.py only,
    where the cache is an optimisation that must never stop the suite
    from collecting.
    """
    import hashlib
    import os

    try:
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            from ..utils.native_build import host_cpu_flags

            flags = host_cpu_flags()
            fp = (
                hashlib.sha256(" ".join(sorted(flags)).encode())
                .hexdigest()[:8]
                if flags else "generic"
            )
            checkout = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            )))
            path = os.path.join(checkout, ".jax_cache", f"isa-{fp}")
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        return path
    except Exception:
        if best_effort:
            return ""
        raise


def host_scalar(x) -> float:
    """Fetch a scalar to host, pod-safe.

    ``float(x)`` on a replicated array whose devices span processes raises
    ("spans non-addressable devices"); the replicated value is present in
    this process's addressable shard, so read it from there.
    """
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        import numpy as np

        return float(np.asarray(x.addressable_shards[0].data))
    return float(x)


def memory_stats() -> dict:
    """Per-device memory stats where the backend exposes them (TPU does)."""
    stats = {}
    for d in jax.local_devices():
        try:
            stats[str(d)] = d.memory_stats()
        except Exception:  # pragma: no cover - backend-dependent
            stats[str(d)] = None
    return stats


def _device_stat(key: str, device: Optional[int]) -> int:
    # one backend-quirk guard: memory_stats() already wraps the
    # per-device call; insertion order follows jax.local_devices()
    stats = list(memory_stats().values())
    picked = stats if device is None else [stats[device]]
    return sum(int((s or {}).get(key, 0)) for s in picked)


def memory_allocated(device: Optional[int] = None) -> int:
    """Live HBM bytes (torch.cuda.memory_allocated call shape): one
    device's, or summed over local devices when ``device`` is None."""
    return _device_stat("bytes_in_use", device)


def max_memory_allocated(device: Optional[int] = None) -> int:
    """Peak HBM bytes since process start (torch.cuda.max_memory_allocated
    call shape). TPU backends report ``peak_bytes_in_use``; backends
    without it return 0 rather than raising."""
    return _device_stat("peak_bytes_in_use", device)


def memory_summary() -> str:
    """Human-readable per-device HBM table (torch.cuda.memory_summary
    call shape) — the first tool to reach for on an XLA OOM: it shows
    live/peak/limit per chip so you can see which of params, optimizer
    state, or saved activations is eating the budget before reading an
    allocation dump."""
    lines = ["device                     in_use      peak     limit"]
    for name, s in memory_stats().items():
        s = s or {}

        def gb(key):
            v = s.get(key)
            return f"{v / 1e9:8.2f}G" if v is not None else "       ?"

        lines.append(
            f"{name:24s} {gb('bytes_in_use')} {gb('peak_bytes_in_use')} "
            f"{gb('bytes_limit')}"
        )
    return "\n".join(lines)
