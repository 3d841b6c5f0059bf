"""Span timers — stage-level wall/device timing into the metrics registry.

The NVTX analog in :mod:`raft_tpu.core.tracing` labels profiler
timelines but *records* nothing; a :class:`span` additionally times the
covered region and writes a ``span.<dotted.name>`` histogram (seconds)
into the registry, so per-stage latency is readable in process.

Semantics:

- **Off by default, near-zero when off.** ``span.__enter__``/``__exit__``
  check one module flag and return — no clock read, no lock, no JAX
  import, and critically NO sync points, so production dispatch stays
  fully async (verified by tests/test_obs.py).
- **Nested spans dot-join**: a ``span("scan")`` inside ``span("search")``
  inside the traced ``ivf_pq`` entry records under
  ``span.ivf_pq.search.scan``. The stack is thread-local.
- **Sync mode** (``enable(sync=True)``): at span exit, arrays attached
  via :meth:`span.attach` are passed to ``jax.block_until_ready`` before
  the clock stops, so the span measures *device* time, not dispatch
  time. Off by default — syncing at stage boundaries serializes the
  pipeline and is strictly an observability trade.
- **Jit-safe**: under a JAX trace (inside ``jax.jit``), spans disable
  themselves — a host timer inside a traced function would measure
  trace time once and nothing on cached calls, and blocking on tracers
  would be an error.
- **On the profiler's timeline**: while a ``jax.profiler`` session
  collects, every span also opens a ``TraceAnnotation`` named
  ``raft_tpu.<dotted.name>``, whether recording is on or not, so its
  interval lands on the host lines of the device trace beside the
  device's ops (one check when no profiler collects). A span's timeline
  event takes its start from ``time.time_ns()`` at enter — the realtime
  clock the profiler's host events are kept on — so exported events
  line up with the profiler's trace.
- Device time per stage of a fused search program is not a span's
  business: the programs name their stages with in-trace scopes
  (:func:`raft_tpu.core.tracing.annotate`), read from a device trace.

Env: ``RAFT_TPU_OBS=1`` enables at import; ``RAFT_TPU_OBS_SYNC=1`` and
``RAFT_TPU_OBS_EVENTS=1`` (timeline event recording into
:mod:`raft_tpu.obs.trace`) add the respective modes.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Optional

from raft_tpu.obs import metrics as _metrics

_enabled = False
_sync = False
_hbm_sample = True
_events = False
_registry: Optional[_metrics.MetricsRegistry] = None

_tls = threading.local()

#: ``jax.profiler.TraceAnnotation``, bound once JAX is imported
_annotation = None


def _stack():
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def enable(sync: bool = False,
           registry: Optional[_metrics.MetricsRegistry] = None,
           hbm: bool = True, events: bool = False) -> None:
    """Turn span recording on. ``sync`` blocks on attached arrays at span
    exit (device time); ``registry`` overrides the global sink;
    ``hbm`` samples HBM gauges at root-span exit; ``events``
    additionally appends one timeline event per span exit (plus HBM
    counter samples) into the :mod:`raft_tpu.obs.trace` ring buffer for
    Chrome-trace/Perfetto export."""
    global _enabled, _sync, _registry, _hbm_sample, _events
    _sync = bool(sync)
    _registry = registry
    _hbm_sample = bool(hbm)
    _events = bool(events)
    _enabled = True


def disable() -> None:
    global _enabled, _sync, _registry, _events
    _enabled = False
    _sync = False
    _events = False
    _registry = None


def _state():
    """Snapshot the enable state (for save/restore around a temporary
    enable — e.g. the bench's diagnostic capture must not wipe a
    RAFT_TPU_OBS=1 enable the user installed at import)."""
    return (_enabled, _sync, _registry, _hbm_sample, _events)


def _restore(state) -> None:
    global _enabled, _sync, _registry, _hbm_sample, _events
    _enabled, _sync, _registry, _hbm_sample, _events = state


def enabled() -> bool:
    return _enabled


def sync_enabled() -> bool:
    return _enabled and _sync


def events_enabled() -> bool:
    return _enabled and _events


def profiling() -> bool:
    """True while a ``jax.profiler`` session collects host events. Never
    imports JAX: with JAX not imported, no session can be collecting."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation.is_enabled()


def registry() -> _metrics.MetricsRegistry:
    """The registry spans currently record into."""
    return _registry if _registry is not None else _metrics.get_registry()


def current_name() -> str:
    """Dotted name of the innermost open span ('' outside any span)."""
    return ".".join(_stack())


def count_dispatch(name: str, impl: str, **labels: str) -> None:
    """Count one dispatch decision under ``<name>.dispatch{impl=...}`` —
    the #1 thing perf triage asks ("which engine actually ran?"). Free
    when recording is off. Counted per DISPATCH DECISION: once per jit
    trace for jitted callers (the choice is baked into the compiled
    program), once per call in eager dispatchers (``ivf_pq.search``'s
    scan-tier pick, ``select_k``'s engine pick). Extra keyword labels
    ride along (e.g. ``filtered="1"`` on a filtered fused-scan
    dispatch)."""
    if _enabled:
        registry().inc(name + ".dispatch", labels={"impl": impl, **labels})


def count_fallback(name: str, reason: str) -> None:
    """Count one *declined* preferred tier under
    ``<name>.fallback{reason=...}`` — the companion of
    :func:`count_dispatch`: the dispatch counter says which engine ran,
    this one says WHY the preferred tier did not (filter-blind kernel,
    memory guard, unsupported layout, ...). Free when recording is off;
    counted per dispatch decision, like count_dispatch."""
    if _enabled:
        registry().inc(name + ".fallback", labels={"reason": reason})


def env_flag(name: str) -> bool:
    """Parse a boolean env var: unset, '', '0', 'false', 'off', 'no' are
    False; anything else is True (plain string truthiness would read
    ``RAFT_TPU_OBS=0`` as enabled)."""
    # the canonical flag parser — the one raw read GL02 points everyone at
    return os.environ.get(name, "").strip().lower() not in (  # graftlint: disable=GL02
        "", "0", "false", "off", "no")


def env_tristate(name: str, default: str = "auto") -> str:
    """Parse a tri-state env var into ``"auto"`` / ``"on"`` / ``"off"``.

    The shared parser for the ``RAFT_TPU_PALLAS_*`` dispatch overrides:
    ``0/false/off/no/never`` → "off", ``1/true/on/yes/always`` → "on",
    unset/''/``auto`` → ``default``. The legacy ``always``/``never``
    spellings stay valid — they were the documented values before this
    helper existed. Unknown values fall back to ``default`` rather than
    silently enabling (same conservatism as :func:`env_flag`)."""
    raw = os.environ.get(name, "").strip().lower()  # graftlint: disable=GL02
    if raw in ("0", "false", "off", "no", "never"):
        return "off"
    if raw in ("1", "true", "on", "yes", "always"):
        return "on"
    return default


#: JAX's ``trace_state_clean``, looked up once (a failed lookup per
#: span costs more than the rest of the span)
_clean_fn = None


def _trace_clean() -> bool:
    """True outside any JAX trace (safe to time / block)."""
    global _clean_fn
    if _clean_fn is None:
        try:
            import jax

            _clean_fn = jax.core.trace_state_clean
        except Exception:
            try:  # newer jax drops it from the public namespace
                from jax._src import core as _jax_core

                _clean_fn = _jax_core.trace_state_clean
            except Exception:
                # unknown jax: assume we ARE under a trace — spans go
                # quiet, but timing/blocking a tracer would be worse
                # than missing samples
                _clean_fn = lambda: False  # noqa: E731
    return _clean_fn()


class span:
    """Context manager timing one stage. Usage::

        with span("scan") as sp:
            out = scan_program(...)
            sp.attach(out)          # blocked on at exit in sync mode

    Arrays may also be passed at construction: ``span("scan", out)``.
    ``labels`` (and :meth:`annotate`) attach key/values that ride into
    the timeline event's ``args`` when event recording is on.
    ``timeline=False`` opens no profiler annotation of the span's own,
    for a caller that has opened one under the same name
    (:func:`raft_tpu.core.tracing.traced`).
    """

    __slots__ = ("name", "_arrays", "_t0", "_wall_ns", "_live", "_open",
                 "_labels", "_timeline", "_ann")

    def __init__(self, name: str, *arrays: Any,
                 labels: Optional[dict] = None, timeline: bool = True):
        self.name = name
        self._arrays = list(arrays)
        self._t0 = 0.0
        self._wall_ns = 0
        self._live = False      # recording into the registry
        self._open = False      # on the name stack
        self._labels = labels
        self._timeline = timeline
        self._ann = None

    def attach(self, *arrays: Any) -> "span":
        """Register arrays (any pytrees) to block on at exit when sync
        mode is on. No-op (and free) when spans are disabled."""
        if self._live and _sync:
            self._arrays.extend(arrays)
        return self

    def annotate(self, **labels: Any) -> "span":
        """Attach labels to this span's timeline event (event recording
        only). No-op (and free) when spans/events are disabled."""
        if self._live and _events:
            if self._labels is None:
                self._labels = {}
            self._labels.update(labels)
        return self

    def __enter__(self) -> "span":
        profiled = profiling()
        if not (_enabled or profiled) or not _trace_clean():
            return self
        stack = _stack()
        stack.append(self.name)
        self._open = True
        if profiled and self._timeline:
            self._ann = _annotation("raft_tpu." + ".".join(stack))
            self._ann.__enter__()
        if _enabled:
            self._live = True
            # the event's start, on the realtime clock of the profiler's
            # host events
            self._wall_ns = time.time_ns()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self._open:
            return False
        stack = _stack()
        try:
            # a raising block yields a truncated duration (and in sync
            # mode one with no device time) — don't mix it into the
            # same series as successful samples
            if self._live and exc_type is None:
                if _sync and self._arrays:
                    import jax

                    jax.block_until_ready(self._arrays)
                dt = time.perf_counter() - self._t0
                reg = registry()
                dotted = ".".join(stack)
                reg.histogram("span." + dotted).observe(dt)
                events = None
                if _events:
                    from raft_tpu.obs import trace as _trace

                    events = _trace.get_buffer()
                    args = self._labels
                    # request-scoped propagation (ISSUE 15): a span
                    # recorded while a RequestContext is installed on
                    # this thread carries the request's trace id(s) —
                    # the stage emits its usual event, the identity
                    # rides along, and obsdump --slowest can reassemble
                    # one request's full timeline
                    ctx = _trace.current_request()
                    if ctx is not None:
                        args = {**(args or {}), **ctx.event_labels()}
                    events.record_span(dotted, self._wall_ns * 1e-9, dt,
                                       args=args)
                # sample HBM only at ROOT-span exit: memory_stats() is a
                # runtime call, and at a child-span exit every
                # ancestor's clock is still
                # running — sampling there would inflate parent timings
                if _hbm_sample and len(stack) == 1:
                    from raft_tpu.obs import hbm as _hbm

                    _hbm.sample(reg, events=events)
        finally:
            if self._ann is not None:
                self._ann.__exit__(exc_type, exc, tb)
                self._ann = None
            stack.pop()
            self._open = self._live = False
            self._arrays = []
        return False


if env_flag("RAFT_TPU_OBS"):  # pragma: no cover - env-driven
    enable(sync=env_flag("RAFT_TPU_OBS_SYNC"),
           events=env_flag("RAFT_TPU_OBS_EVENTS"))
