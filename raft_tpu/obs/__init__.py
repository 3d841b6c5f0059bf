"""raft_tpu.obs — in-process observability: metrics, span timers, HBM.

The reference attributes time through NVTX ranges + external profilers;
this package makes the same attribution available *in process*:

- :mod:`raft_tpu.obs.metrics` — thread-safe counters/gauges/histograms
  with labels, ``snapshot()`` → dict, ``dump_jsonl`` sink;
- :mod:`raft_tpu.obs.spans`   — ``span(name)`` stage timers (dotted
  nesting, optional device-time sync), recorded into the registry and,
  while a profiler collects, annotated on its timeline;
- :mod:`raft_tpu.obs.hbm`     — ``device.memory_stats()`` telemetry,
  sampled per local device;
- :mod:`raft_tpu.obs.prof`    — compiled-program cost attribution
  (``Compiled.cost_analysis``), roofline memory-/compute-bound
  classing against a device peak table, and a programmatic
  ``jax.profiler`` start/stop bracket;
- :mod:`raft_tpu.obs.trace`   — span-event ring buffer +
  Chrome-trace/Perfetto export (``obs.enable(events=True)``);
- :mod:`raft_tpu.obs.flight`  — flight recorder: crash-surviving dumps
  of events + metrics + logs on signals/atexit/periodically;
- :mod:`raft_tpu.obs.expo`    — live telemetry exposition: stdlib HTTP
  endpoint serving Prometheus text-format ``/metrics``, ``/healthz``,
  on-demand ``/flightz`` dumps, and ``/indexz`` index health;
- :mod:`raft_tpu.obs.quality` — online recall estimation: a shadow
  verifier reservoir-samples live requests and replays them through
  exact brute force on host, publishing ``quality.recall`` gauges with
  Wilson confidence intervals;
- :mod:`raft_tpu.obs.index_stats` — index-health introspection:
  list-size skew, dead centroids, centroid drift, PQ quantization
  error, tombstone density, as ``index.*`` gauges + ``/indexz``;
- :mod:`raft_tpu.obs.fleet`   — pod-wide aggregation: merges per-host
  flight dumps (shared run_id, clock alignment) and attributes
  collective-timing stragglers;
- :mod:`raft_tpu.obs.sanitize` — runtime sanitizer harness
  (``RAFT_TPU_SANITIZE=1``): rank-promotion/NaN config, transfer-guard
  scopes, and a jit-cache-miss counter with budget assertions.

Everything is off by default and adds no sync points until
:func:`enable` is called (or ``RAFT_TPU_OBS=1`` is set). See
docs/observability.md.
"""

from raft_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    exemplars_for_quantile,
    get_registry,
    load_jsonl,
    quantile_from_state,
    set_registry,
)
from raft_tpu.obs.trace import (  # noqa: F401
    RequestContext,
    current_request,
    new_trace_id,
    use_request,
)
from raft_tpu.obs.spans import (  # noqa: F401
    count_dispatch,
    count_fallback,
    current_name,
    disable,
    enable,
    enabled,
    env_flag,
    env_tristate,
    events_enabled,
    registry,
    profiling,
    span,
    sync_enabled,
)
from raft_tpu.obs import hbm  # noqa: F401
from raft_tpu.obs import prof  # noqa: F401
from raft_tpu.obs import trace  # noqa: F401
from raft_tpu.obs import flight  # noqa: F401
from raft_tpu.obs import expo  # noqa: F401
from raft_tpu.obs import quality  # noqa: F401
from raft_tpu.obs import index_stats  # noqa: F401
from raft_tpu.obs import fleet  # noqa: F401
from raft_tpu.obs import sanitize  # noqa: F401
