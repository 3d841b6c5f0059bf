"""raft_tpu.robust — fault injection, retries, degradation, resumable builds.

The robustness layer (ISSUE 7): production ANN serving treats build
resumability and graceful degradation as table stakes, and every
recovery path must be CI-testable instead of outage-tested.

- :mod:`raft_tpu.robust.faults`     — named fault points
  (``faultpoint("build.chunk_encode")``) driven by an env/JSON fault
  plan (raise-OOM / SIGTERM-self / sleep / NaN / force-decline);
- :mod:`raft_tpu.robust.retry`      — the unified retry policy:
  exponential backoff + jitter, deadline budgets,
  ``retry.attempts{site=}`` counters, and the request-scoped
  :class:`~raft_tpu.robust.retry.Deadline` shared budget that serving
  threads through queue wait + dispatch + retries (ISSUE 14);
- :mod:`raft_tpu.robust.degrade`    — the RESOURCE_EXHAUSTED
  degradation ladder (halve batch → bf16 LUT → fp8 LUT → decline fused tier →
  host gather) with ``degrade.steps{from=,to=,reason=}`` counters;
- :mod:`raft_tpu.robust.checkpoint` — atomic (tmp+fsync+rename) build
  manifests + encoded-list shards behind
  ``ivf_pq.build_chunked(checkpoint_dir=..., resume=...)``.

``faults`` and ``retry`` are stdlib-only at import. Everything is
inert until a fault plan is
installed / a retry policy is invoked; fault points cost one None check
when no plan is active. See docs/developer_guide.md "Robustness".
"""

from raft_tpu.robust import checkpoint, degrade, faults, retry  # noqa: F401
