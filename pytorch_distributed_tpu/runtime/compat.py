"""Two probes of jax internals that have no public spelling, kept in one
place so a jax upgrade has one file to check."""

from __future__ import annotations


def jit_cache_size(fn):
    """Compiled-specialization count of a jitted callable, or None.

    The recompile sentinel (runtime/tracing.py) polls this after each
    step: a steady-state loop whose count grows is silently recompiling.
    ``_cache_size`` is private jax API on the jitted-function object; a
    callable without it (a host-loop step that is not one jit) reports
    None — sentinel off for that callable.
    """
    f = getattr(fn, "_cache_size", None)
    if not callable(f):
        return None
    return int(f())


def live_buffer_bytes():
    """Live device-buffer bytes.

    TPU/GPU backends expose per-device ``memory_stats()['bytes_in_use']``
    — the allocator's own number, preferred. XLA:CPU reports no memory
    stats, so the fallback sums ``nbytes`` over ``jax.live_arrays()``
    (committed arrays only — it cannot see donated/internal scratch, but
    it tracks the leak shapes that matter: caches, states, stale
    references). Sampled at log cadence only; never on the step path.
    """
    import jax

    total, saw = 0, False
    for d in jax.local_devices():
        try:
            s = d.memory_stats()
        except Exception:  # pragma: no cover - backend-dependent
            s = None
        if s and "bytes_in_use" in s:
            total += int(s["bytes_in_use"])
            saw = True
    if saw:
        return total
    return int(sum(a.nbytes for a in jax.live_arrays()))
