"""Tracing annotations — the nvtx analog.

The reference wraps every major API in a scoped NVTX range
(``raft::common::nvtx::range``, core/nvtx.hpp:96-144 — e.g.
select_k-inl.cuh:289, ivf_pq_build.cuh:130), zero-cost unless profiling.
The TPU equivalents are:

- :func:`jax.named_scope` — labels the XLA ops traced inside the scope,
  so kernels show up under the API name in XProf/Perfetto op profiles;
- :class:`jax.profiler.TraceAnnotation` — a host-side span on the
  profiler timeline covering dispatch + host orchestration.

:func:`traced` applies both. Like NVTX, the cost when no profiler is
attached is negligible (a context-manager enter/exit per call), and the
XLA metadata is baked in at trace time only.

When the observability layer is enabled (:func:`raft_tpu.obs.enable`),
or a profiler collects, ``traced`` additionally opens a :func:`span`
named after the API (``raft_tpu.`` prefix stripped), so every traced
entry point's wall time lands in the metrics registry and nested stage
spans report under dotted names like ``ivf_pq.build.train`` — on the
profiler's timeline as ``raft_tpu.ivf_pq.build.train``. The entry
point's own annotation is the ``TraceAnnotation`` above, so its span
opens none of its own. In sync mode the function's outputs are
attached, so the span measures device time. With observability off and
no profiler collecting this adds two flag checks per call — no clock
reads, no sync points.

Inside a jitted search program, :func:`annotate` names each stage
(``raft_tpu.brute_force.distance``, ``raft_tpu.ivf_pq.scan``): it
changes only the ``op_name`` metadata of the compiled program, so a
device trace splits the program's time by stage at no cost to the
program.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from raft_tpu.obs import spans as _spans
from raft_tpu.obs.spans import span  # noqa: F401  (re-export: the stage timer)


def annotate(name: str):
    """Named-scope annotation for code that is ALREADY inside a trace
    (shard_map/jit bodies — the collectives in ``parallel/comms.py``,
    the stages of the search programs): labels the lowered XLA ops so
    they group under ``name`` in XProf/Perfetto op profiles and in the
    ``op_name`` of the compiled program's instructions. Works as a
    context manager and as a decorator of a function traced under jit.
    A scope opened outside a jitted function does not reach its ops.
    The host-side halves of :func:`traced` (TraceAnnotation, recording
    spans) are meaningless there — a traced body runs once at trace
    time — so this is just the metadata half."""
    return jax.named_scope(name)


def traced(name: Optional[str] = None) -> Callable:
    """Decorator: run the function under a named profiler scope
    (reference: RAFT_USING_NVTX / nvtx::range at API entry), plus a
    recording span when observability is enabled.

    Works with and without parentheses:

    >>> @traced("raft_tpu.select_k")
    ... def select_k(...): ...
    >>> @traced
    ... def helper(...): ...
    """
    if callable(name):  # bare @traced form
        return traced(None)(name)

    def deco(fn):
        label = name or f"raft_tpu.{fn.__qualname__}"
        span_name = label[len("raft_tpu."):] if label.startswith("raft_tpu.") \
            else label

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.profiler.TraceAnnotation(label), jax.named_scope(label):
                if not (_spans.enabled() or _spans.profiling()):
                    return fn(*args, **kwargs)
                with span(span_name, timeline=False) as sp:
                    out = fn(*args, **kwargs)
                    sp.attach(out)
                    return out

        return wrapper

    return deco
