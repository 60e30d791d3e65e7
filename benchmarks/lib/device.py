"""The device's own memory counters, read the same way by every job kind."""

from __future__ import annotations


def device_memory() -> dict:
    """``memory_stats()`` of this process's first chip, numbers only."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return {k: int(v) for k, v in stats.items() if isinstance(v, (int, float))}


def measured_peak(stats: dict) -> int:
    """``memory_peak_bytes``: the allocator's peak of live buffers plus the
    peak reserved for loaded programs' temporaries.  Both are the device
    runtime's counters; nothing here comes from the compiler.  The two are
    disjoint regions (``bytes_reservable_limit`` falls by what buffers take)
    and a program's reservation stands from its loading on, so the sum is
    what was committed when the buffers peaked; PERF.md has the chip's
    readings phase by phase."""
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))
