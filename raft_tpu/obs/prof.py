"""Cost attribution — XLA compiled-program analysis + roofline classing.

The obs stack's first two legs (PRs 1 and 5) say *how long* a stage
took; this leg says whether that time is anywhere near the hardware
limit. The reference gets per-kernel attribution from nsys/NVTX; the
TPU-native equivalents are XLA's compiled cost model
(``Compiled.cost_analysis()`` / ``memory_analysis()``) and the
programmatic ``jax.profiler`` bracket — both wrapped here,
version-tolerant and CPU-degrading like :mod:`raft_tpu.obs.hbm` (every
helper returns ``{}``/``None`` instead of raising, so instrumented
code runs identically on the CPU test mesh).

Pieces:

- :func:`cost_analysis` / :func:`memory_analysis` — normalize the
  ``Compiled`` accessors across jax versions (dict vs list-of-dict vs
  absent) into plain dicts;
- :func:`device_peak` — a peak flops/HBM-bandwidth table per device
  kind (v5e/v5p/v4 + an explicit CPU placeholder) with the roofline
  ridge point ``peak_flops / peak_bw``;
- :func:`analyze_compiled` / :func:`analyze_jit` — derive per-program
  flops, bytes-accessed, and arithmetic intensity, classify memory- vs
  compute-bound against the peak table, and (given a measured elapsed
  time) the achieved-bandwidth / achieved-flops fractions;
- :func:`record` — emit ``prof.flops`` / ``prof.bytes`` /
  ``prof.arith_intensity`` / ``prof.achieved_bw_frac`` gauges (plus a
  labeled ``prof.bound`` marker) into a metrics registry — the series
  ``tools/obsdump.py`` renders and the bench detail rows are built
  from;
- :class:`capture` — a start/stop programmatic profiler bracket
  generalizing the one-shot ``RAFT_TPU_XPROF_DIR`` block that lived in
  ``bench/runner.py``.

The numbers are XLA's *static* cost model: flops are algorithmic
(fusion does not change them), bytes-accessed is the compiler's
estimate of HBM traffic for the fused program. They bound reality from
below — a program whose achieved bandwidth fraction is already near
1.0 has nothing left to fuse, which is exactly the question
("runs as fast as the hardware allows") the ROADMAP needs answered per
recorded row.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

__all__ = [
    "DevicePeak", "DEVICE_PEAKS", "device_peak", "peak_for_kind",
    "InterconnectPeak", "INTERCONNECT_PEAKS", "interconnect_peak",
    "axis_peak_bw",
    "cost_analysis", "memory_analysis", "ProgramCost",
    "analyze_compiled", "analyze_jit", "record", "capture",
]


# ---------------------------------------------------------------------------
# device peak table (roofline ceilings)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Peak dense compute (FLOP/s, bf16 MXU for TPUs) and HBM bandwidth
    (bytes/s) for one device kind. ``ridge`` is the roofline ridge
    point in flops/byte: programs whose arithmetic intensity sits below
    it are memory-bound on this part."""

    name: str
    flops: float
    hbm_bw: float
    placeholder: bool = False

    @property
    def ridge(self) -> float:
        return self.flops / self.hbm_bw


# Published per-chip peaks (dense bf16 matmul, HBM bandwidth). The CPU
# entry is an explicit PLACEHOLDER for the CPU backend only — the CI
# mesh needs the classification machinery to run, not to be calibrated;
# rows it produces still carry real flops/bytes from the XLA cost model.
DEVICE_PEAKS: Dict[str, DevicePeak] = {
    "v4": DevicePeak("v4", 275e12, 1228e9),
    "v5e": DevicePeak("v5e", 197e12, 819e9),
    "v5p": DevicePeak("v5p", 459e12, 2765e9),
    "cpu": DevicePeak("cpu", 5e10, 2e10, placeholder=True),
}


def _peak_key(kind: Optional[str]) -> str:
    """Table key for a PJRT ``device_kind`` string. Matching is
    substring-based over the normalized kind ("TPU v5 lite" and
    "TPU v5e" both mean v5e); "cpu" is the CPU backend's own kind. Any
    other kind raises: a device missing from the table has no published
    peaks, and placeholder ones would mislabel its roofline."""
    k = (kind or "").lower().replace(" ", "")
    if "v5p" in k or "v5pod" in k:
        return "v5p"
    if "v5e" in k or "v5lite" in k or "v5litepod" in k:
        return "v5e"
    if "v4" in k:
        return "v4"
    if k == "cpu":
        return "cpu"
    raise ValueError(f"no published peaks for device kind {kind!r}")


def peak_for_kind(kind: str) -> DevicePeak:
    """Map a PJRT ``device_kind`` string to its peak entry (see
    :func:`_peak_key`; unknown kinds raise ``ValueError``)."""
    return DEVICE_PEAKS[_peak_key(kind)]


def device_peak(device: Optional[Any] = None) -> DevicePeak:
    """Peak entry for ``device`` (default: device 0)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return peak_for_kind(device.device_kind)


@dataclasses.dataclass(frozen=True)
class InterconnectPeak:
    """Per-axis interconnect bandwidth ceiling for one device kind:
    ``ici_bw`` is per-link ICI bandwidth (bytes/s, one direction),
    ``dcn_bw`` the per-host DCN bandwidth share — the two denominators
    of the per-axis comm roofline (``comms.bytes{axis=...}`` / peak).
    Like :class:`DevicePeak` these are order-of-magnitude published
    figures, not calibrations; the CPU entry is a placeholder for the
    CPU backend so the CI mesh can exercise the classification."""

    name: str
    ici_bw: float
    dcn_bw: float
    placeholder: bool = False


# Published per-link ICI and per-host DCN figures (one direction,
# order of magnitude — e.g. v4 ICI ≈ 300 GB/s per link; DCN shares
# ≈ 25 GB/s/host across generations). The asymmetry RATIO is what the
# per-axis roofline needs to be honest about: an axis=dcn byte is
# ~10× more expensive than an axis=ici byte.
INTERCONNECT_PEAKS: Dict[str, InterconnectPeak] = {
    "v4": InterconnectPeak("v4", 300e9, 25e9),
    "v5e": InterconnectPeak("v5e", 200e9, 25e9),
    "v5p": InterconnectPeak("v5p", 600e9, 25e9),
    "cpu": InterconnectPeak("cpu", 1e9, 1e8, placeholder=True),
}


def interconnect_peak(kind: Optional[str] = None) -> InterconnectPeak:
    """Interconnect peak entry for a PJRT ``device_kind`` string
    (default: device 0's kind). Same matching as :func:`peak_for_kind`:
    unknown kinds raise."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    return INTERCONNECT_PEAKS[_peak_key(kind)]


def axis_peak_bw(axis: str, peak: Optional[InterconnectPeak] = None
                 ) -> float:
    """Bandwidth ceiling for one ``comms.bytes{axis=...}`` label: the
    DCN figure when the axis name is DCN-labeled
    (:func:`raft_tpu.parallel.mesh.is_dcn_axis` — imported lazily, obs
    must not import parallel at module scope), the ICI figure
    otherwise. On a jax-less triage host (obsdump reading a dump) the
    parallel package won't import; fall back to the same name-prefix
    rule ``is_dcn_axis`` applies (mesh.DCN_AXIS_PREFIXES)."""
    try:
        from raft_tpu.parallel.mesh import is_dcn_axis

        dcn = is_dcn_axis(axis)
    except Exception:
        dcn = str(axis).lower().startswith(("dcn", "pod", "slice"))
    p = peak if peak is not None else interconnect_peak()
    return p.dcn_bw if dcn else p.ici_bw


# ---------------------------------------------------------------------------
# version-tolerant Compiled accessors
# ---------------------------------------------------------------------------

def cost_analysis(compiled: Any) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` normalized to one plain dict.
    Handles every shape jax has shipped — a dict, a one-element list of
    dicts (0.4.x), or the method missing/raising (old jax, exotic
    backends) — by degrading to ``{}``."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return {}
    return {str(k): float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def memory_analysis(compiled: Any) -> Dict[str, int]:
    """``Compiled.memory_analysis()`` (a ``CompiledMemoryStats``-like
    object or dict) flattened to ``{field: int}``; ``{}`` when the
    backend doesn't report."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    if isinstance(ma, dict):
        return {str(k): int(v) for k, v in ma.items()
                if isinstance(v, (int, float))}
    out: Dict[str, int] = {}
    for field in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "temp_size_in_bytes"):
        v = getattr(ma, field, None)
        if isinstance(v, (int, float)):
            out[field] = int(v)
    return out


# ---------------------------------------------------------------------------
# roofline derivation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramCost:
    """Static cost + roofline classification of one compiled program.

    ``flops``/``bytes_accessed`` come from XLA's cost model;
    ``arithmetic_intensity = flops / bytes_accessed`` (flops/byte);
    ``bound`` is ``"memory"`` or ``"compute"`` against the device
    ridge. The achieved fractions are only set when a measured
    ``elapsed_s`` was supplied (see :meth:`attribute_elapsed`) — they
    compare realized bandwidth/compute against the peak table."""

    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    arithmetic_intensity: Optional[float] = None
    bound: Optional[str] = None
    device_kind: str = "cpu"
    peak_flops: float = 0.0
    peak_bw: float = 0.0
    ridge: float = 0.0
    peak_is_placeholder: bool = True
    memory: Dict[str, int] = dataclasses.field(default_factory=dict)
    elapsed_s: Optional[float] = None
    achieved_bw_frac: Optional[float] = None
    achieved_flops_frac: Optional[float] = None

    def attribute_elapsed(self, elapsed_s: Optional[float]) -> "ProgramCost":
        """Fold a measured wall time in: achieved bandwidth =
        ``bytes_accessed / elapsed_s`` as a fraction of peak (same for
        flops). No-op on None/zero elapsed."""
        if not elapsed_s or elapsed_s <= 0:
            return self
        self.elapsed_s = float(elapsed_s)
        if self.bytes_accessed and self.peak_bw:
            self.achieved_bw_frac = (
                self.bytes_accessed / elapsed_s) / self.peak_bw
        if self.flops and self.peak_flops:
            self.achieved_flops_frac = (
                self.flops / elapsed_s) / self.peak_flops
        return self

    def as_row(self) -> Dict[str, Any]:
        """The bench detail-row columns (rounded for record hygiene)."""
        out: Dict[str, Any] = {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "bound": self.bound,
        }
        if self.arithmetic_intensity is not None:
            out["arith_intensity"] = round(self.arithmetic_intensity, 4)
        if self.achieved_bw_frac is not None:
            out["achieved_bw_frac"] = round(self.achieved_bw_frac, 6)
        if self.achieved_flops_frac is not None:
            out["achieved_flops_frac"] = round(self.achieved_flops_frac, 6)
        return out


def analyze_compiled(compiled: Any, device: Optional[Any] = None,
                     elapsed_s: Optional[float] = None) -> ProgramCost:
    """Derive a :class:`ProgramCost` from a ``jax.stages.Compiled``:
    flops/bytes from the cost model, memory stats, roofline bound
    against :func:`device_peak`, achieved fractions when ``elapsed_s``
    is given. Degrades field-by-field — a backend without a cost model
    still yields the peak/ridge context with None costs."""
    peak = device_peak(device)
    ca = cost_analysis(compiled)
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed", ca.get("bytes_accessed"))
    ai = None
    bound = None
    if flops is not None and bytes_accessed:
        ai = flops / bytes_accessed
        bound = "memory" if ai < peak.ridge else "compute"
    cost = ProgramCost(
        flops=flops, bytes_accessed=bytes_accessed,
        arithmetic_intensity=ai, bound=bound,
        device_kind=peak.name, peak_flops=peak.flops, peak_bw=peak.hbm_bw,
        ridge=peak.ridge, peak_is_placeholder=peak.placeholder,
        memory=memory_analysis(compiled),
    )
    return cost.attribute_elapsed(elapsed_s)


def analyze_jit(fn, *args, device: Optional[Any] = None,
                elapsed_s: Optional[float] = None,
                **jit_kwargs) -> Optional[ProgramCost]:
    """Trace+compile ``fn(*args)`` under ``jax.jit`` and analyze the
    compiled program. The one-call wrapper for whole-API attribution:
    the bench runner points it at its search closure, so the cost of
    THE program the row measured (scan tier, refine tier, epilogue —
    whatever dispatch picked) is what lands in the record. Returns
    ``None`` when the callable cannot be traced end-to-end (host-side
    control flow on values, provider closures) — callers keep their
    row, just without cost columns."""
    try:
        import jax

        compiled = jax.jit(fn, **jit_kwargs).lower(*args).compile()
    except Exception:
        return None
    return analyze_compiled(compiled, device=device, elapsed_s=elapsed_s)


def record(cost: ProgramCost, registry=None,
           program: str = "default") -> None:
    """Write one program's cost into gauges: ``prof.flops`` /
    ``prof.bytes`` / ``prof.arith_intensity`` /
    ``prof.achieved_bw_frac`` / ``prof.achieved_flops_frac`` (labels
    ``{program=...}``) plus a ``prof.bound{program=,bound=}`` marker
    gauge — the series ``tools/obsdump.py``'s roofline table reads.
    Defaults to the live obs registry."""
    if registry is None:
        from raft_tpu.obs import spans as _spans

        registry = _spans.registry()
    # the registry renders labels as name{k=v,k2=v2} with no escaping:
    # a program label carrying , { } (the bench context embeds a search
    # -param dict repr) would corrupt every downstream key parse — map
    # them to lookalikes at this one chokepoint
    program = (str(program).replace(",", ";")
               .replace("{", "(").replace("}", ")"))
    labels = {"program": program}
    if cost.flops is not None:
        registry.gauge("prof.flops", labels).set(cost.flops)
    if cost.bytes_accessed is not None:
        registry.gauge("prof.bytes", labels).set(cost.bytes_accessed)
    if cost.arithmetic_intensity is not None:
        registry.gauge("prof.arith_intensity", labels).set(
            cost.arithmetic_intensity)
    if cost.achieved_bw_frac is not None:
        registry.gauge("prof.achieved_bw_frac", labels).set(
            cost.achieved_bw_frac)
    if cost.achieved_flops_frac is not None:
        registry.gauge("prof.achieved_flops_frac", labels).set(
            cost.achieved_flops_frac)
    if cost.bound is not None:
        registry.gauge("prof.bound",
                       {"program": program, "bound": cost.bound}).set(1.0)


# ---------------------------------------------------------------------------
# programmatic profiler capture
# ---------------------------------------------------------------------------

class capture:
    """Start/stop bracket around ``jax.profiler`` trace collection —
    the generalization of the one-shot ``RAFT_TPU_XPROF_DIR`` block
    that used to live inline in ``bench/runner.py``. Context manager
    or explicit ``start()``/``stop()``::

        cap = prof.capture("/tmp/xprof").start()
        run_workload()
        cap.stop()            # returns the log dir (None if never armed)

    Never raises: a backend without profiler support, a second
    concurrent capture (jax allows one trace at a time), or a broken
    logdir records the failure in ``.error`` and stays inactive —
    the measured workload must not pay for its own diagnostics."""

    def __init__(self, logdir: Optional[str] = None):
        if logdir is None:
            logdir = os.environ.get("RAFT_TPU_XPROF_DIR", "")  # path value
            if not logdir.strip():
                logdir = "/tmp/raft_tpu_xprof"
        self.logdir = logdir
        self.active = False
        self.error: Optional[BaseException] = None

    def start(self) -> "capture":
        if self.active:
            return self
        try:
            import jax

            jax.profiler.start_trace(self.logdir)
            self.active = True
        except Exception as e:
            self.error = e
        return self

    def stop(self) -> Optional[str]:
        if not self.active:
            return None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            self.error = e
        finally:
            self.active = False
        return self.logdir

    def __enter__(self) -> "capture":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop()
        return False
