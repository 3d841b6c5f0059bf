"""HBM telemetry — device memory stats as gauges.

One home for the ``device.memory_stats()`` calls that were previously
ad-hoc (the recon-cache sizing probe buried in ``neighbors/ivf_pq.py``
moved here). TPU/GPU PJRT clients report an allocator dict
(``bytes_in_use``, ``peak_bytes_in_use``, ``bytes_limit``, ...); the CPU
client reports nothing — every helper degrades to ``None``/``{}``
instead of raising, so instrumented code runs identically on the CPU
test mesh.

:func:`sample` writes the readings into a metrics registry
(``hbm.bytes_in_use`` set-to-current, ``hbm.peak_bytes`` high-water) —
the span timers call it at root-span exit when observability is on
(nested-span exits skip it: the ``memory_stats()`` round-trip would
land inside every ancestor span's timed region). By default it samples
EVERY local device into per-device-labeled gauges
(``hbm.bytes_in_use{device=0..n}``) so sharded runs see each chip, with
device 0 mirrored into the unlabeled series for single-chip readers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def device_memory_stats(device: Optional[Any] = None) -> Dict[str, int]:
    """``device.memory_stats()`` with all failure modes collapsed to an
    empty dict (CPU backend, very old jax)."""
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        stats = device.memory_stats()
        return dict(stats) if stats else {}
    except Exception:
        return {}


def bytes_in_use(device: Optional[Any] = None) -> Optional[int]:
    """Live allocated HBM bytes, or None when the backend doesn't report."""
    v = device_memory_stats(device).get("bytes_in_use")
    return int(v) if v is not None else None


def peak_bytes(device: Optional[Any] = None) -> Optional[int]:
    """Allocator high-water mark (process lifetime), or None."""
    v = device_memory_stats(device).get("peak_bytes_in_use")
    return int(v) if v is not None else None


def bytes_limit(device: Optional[Any] = None,
                default: Optional[int] = None) -> Optional[int]:
    """Total HBM the allocator may use (the capacity heuristics' input —
    e.g. the IVF-PQ recon-cache sizing), or ``default``."""
    v = device_memory_stats(device).get("bytes_limit")
    return int(v) if v else default


def _local_devices() -> list:
    try:
        import jax

        return list(jax.local_devices())
    except Exception:
        return []


def _record(registry, stats: Dict[str, int], labels: Optional[Dict],
            events, suffix: str) -> None:
    if "bytes_in_use" in stats:
        registry.gauge("hbm.bytes_in_use", labels).set(stats["bytes_in_use"])
        if events is not None:
            events.record_counter("hbm.bytes_in_use" + suffix,
                                  stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        registry.gauge("hbm.peak_bytes", labels).max(
            stats["peak_bytes_in_use"])
        if events is not None:
            events.record_counter("hbm.peak_bytes" + suffix,
                                  stats["peak_bytes_in_use"])
    if "bytes_limit" in stats:
        registry.gauge("hbm.bytes_limit", labels).set(stats["bytes_limit"])


def note_budget(budget_bytes: int, registry=None) -> None:
    """Mirror an externally-resolved HBM budget into the
    ``hbm.bytes_limit`` gauge family, under its OWN labeled series
    ``{source=admission}``.

    Backends that report no allocator stats (the CPU test mesh) leave
    the ``hbm.*`` family empty — but a serving process still HAS an
    authoritative limit: the one its :class:`~raft_tpu.serve.registry.
    IndexRegistry` admits against. Recording it keeps the exposition
    endpoint's ``hbm_*`` families populated on every backend. The
    distinct label matters on real devices: the unlabeled and
    ``{device=i}`` series belong to :func:`sample`'s allocator
    readings, and a capacity-capped registry (``budget_bytes`` <
    the chip's limit) must not flip-flop those between two meanings."""
    if registry is None:
        from raft_tpu.obs import metrics as _metrics

        registry = _metrics.get_registry()
    registry.gauge("hbm.bytes_limit", {"source": "admission"}).set(
        int(budget_bytes))


def sample(registry=None, device: Optional[Any] = None,
           events=None) -> Dict[str, int]:
    """Record current HBM gauges into ``registry`` (default: the global
    one) and return device 0's raw stats dict ({} when unavailable).

    With ``device=None`` (the span-exit path) EVERY local device is
    sampled into per-device-labeled gauges (``hbm.bytes_in_use{device=i}``
    etc.) so sharded runs see each chip's HBM, and device 0 additionally
    feeds the unlabeled series the bench's peak-HBM column reads. An
    explicit ``device`` samples just that one into the unlabeled series.
    Backends that report nothing (the CPU test mesh) degrade to ``{}``.
    ``events`` (an :class:`raft_tpu.obs.trace.EventBuffer`) additionally
    records one counter-track sample per gauge.
    """
    if registry is None:
        from raft_tpu.obs import metrics as _metrics

        registry = _metrics.get_registry()
    if device is not None:
        stats = device_memory_stats(device)
        if stats:
            _record(registry, stats, None, events, "")
        return stats
    first: Dict[str, int] = {}
    for i, dev in enumerate(_local_devices()):
        stats = device_memory_stats(dev)
        if i == 0:
            first = stats
        if not stats:
            continue
        _record(registry, stats, {"device": str(i)}, events,
                "{device=%d}" % i)
        if i == 0:  # unlabeled back-compat series mirrors device 0
            _record(registry, stats, None, None, "")
    return first
