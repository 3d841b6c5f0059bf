"""Communicator facade — comms_t-shaped API over ``jax.lax`` collectives.

TPU-native re-design of the reference's comms stack (SURVEY.md §5.8):

- abstract ``comms_iface``/``comms_t`` (core/comms.hpp:123,242) → :class:`Comms`,
  a thin named-axis wrapper whose methods are the same verbs (allreduce /
  bcast / reduce / allgather / reducescatter / alltoall / send-recv /
  comm_split) lowered to ``lax.psum`` / ``lax.all_gather`` / ``ppermute`` /
  etc. **Methods must be called inside** ``shard_map`` (or jitted code with
  the axis bound) — XLA then schedules them on ICI/DCN;
- NCCL/UCX backends (comms/std_comms.hpp) → none needed: the XLA runtime is
  the backend;
- bootstrap (raft-dask Comms.init, NCCL uid exchange) →
  :func:`initialize_distributed` wrapping ``jax.distributed.initialize``;
- sub-communicators (core/resource/sub_comms.hpp, comm_split) → operating
  over a subset of mesh axis names;
- stream-sync failure propagation (comms_t::sync_stream, core/comms.hpp:290)
  → XLA surfaces collective failures as program errors; :meth:`sync_stream`
  exists for API parity.

Reduction ops mirror ``op_t`` (core/comms.hpp:36): SUM, PROD, MIN, MAX.

**Comms telemetry** (docs/observability.md): when observability is on
(:func:`raft_tpu.obs.enable`), every collective counts one op and its
per-rank bytes into ``comms.ops{op=...,axis=...}`` /
``comms.bytes{op=...,axis=...}``, labeled by collective verb and axis
name — a 2-axis DCN×ICI mesh attributes traffic per axis. The byte
model charges what each rank actually moves over the interconnect:
fixed-size-result collectives (allreduce, reducescatter, alltoall,
ppermute, send_recv_ring) count their per-rank payload; gather-family
collectives (allgather, gather, bcast, allgatherv, gatherv) count
``axis_size × payload`` — the materialized gathered table every rank
assembles over ICI, the O(n_dev·m·k) cost the ring top-k exchange
exists to avoid; the ring exchange itself (``ring_topk``) counts one op
and one surviving-block payload PER HOP (n_dev−1 hops per merge),
whether the hops ride :meth:`Comms.ring_topk_hop` (ppermute fallback)
or the Pallas kernel's in-kernel remote DMAs (attributed via
:meth:`Comms.count_ring_topk` — no collective escapes telemetry,
GL10). Counting reads only STATIC shape/dtype at trace time (once per
jit trace, the same per-dispatch-decision semantics as
``obs.count_dispatch``): zero host syncs, zero runtime cost in the
compiled program, and a single flag check when observability is off.
Each collective also lowers under a ``raft_tpu.comms.<verb>`` named
scope (``core.tracing.annotate``) so profiler op timelines attribute
ICI/DCN time to the verb.
"""

from __future__ import annotations

import enum
import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.lax import axis_size as _axis_size
from raft_tpu.core.tracing import annotate as _annotate
from raft_tpu.obs import fleet as _fleet
from raft_tpu.obs import sanitize as _sanitize
from raft_tpu.obs import spans as _obs
from raft_tpu.robust import faults as _faults


class Op(enum.Enum):
    """Reduction op (reference: core/comms.hpp:36 ``op_t``)."""

    SUM = "sum"
    PROD = "prod"
    MIN = "min"
    MAX = "max"


class Status(enum.Enum):
    """Collective status (reference: core/comms.hpp:39 ``status_t``)."""

    SUCCESS = 0
    ERROR = 1
    ABORT = 2


_REDUCERS = {
    Op.SUM: lax.psum,
    Op.MAX: lax.pmax,
    Op.MIN: lax.pmin,
}


def _axis_label(axis_name: Union[str, Sequence[str]]) -> str:
    """Canonical label for one axis name or a multi-axis tuple
    (``("dcn", "ici")`` → ``"dcn+ici"``)."""
    if isinstance(axis_name, str):
        return axis_name
    return "+".join(str(a) for a in axis_name)


# Collectives whose RESULT (and interconnect traffic) grows with the
# axis: each rank materializes the size×payload gathered table, so the
# byte model scales their payload by the static axis size.
_GATHER_FAMILY = frozenset(
    {"allgather", "gather", "bcast", "allgatherv", "gatherv"})


def _payload_bytes(*arrays) -> int:
    """Per-rank payload bytes from STATIC shape/dtype — works on
    tracers (shapes are always concrete under shard_map), never touches
    values, so counting introduces no host syncs (GL01-clean)."""
    total = 0
    for a in arrays:
        shape = getattr(a, "shape", None)
        if shape is None:  # python scalar payload
            total += 8
            continue
        dtype = getattr(a, "dtype", None)
        try:
            itemsize = np.dtype(dtype).itemsize
        except TypeError:
            itemsize = 4
        total += int(math.prod(shape)) * itemsize
    return total


class Comms:
    """Named-axis communicator (reference: ``comms_t``, core/comms.hpp:242).

    Bound to one or more mesh axis names; all methods are collective and must
    run inside the matching ``shard_map``/``pjit`` scope.
    """

    def __init__(self, axis_name: Union[str, Sequence[str]]):
        self.axis_name = axis_name

    # -- topology ----------------------------------------------------------
    def get_size(self) -> jax.Array:
        return _axis_size(self.axis_name)

    def get_rank(self) -> jax.Array:
        return lax.axis_index(self.axis_name)

    def comm_split(self, axis_name: Union[str, Sequence[str]]) -> "Comms":
        """Sub-communicator over a subset of mesh axes (reference:
        comms_t::comm_split, std_comms.hpp:145 — here: zero-cost renaming)."""
        return Comms(axis_name)

    # -- telemetry ---------------------------------------------------------
    def _count(self, op_name: str, *arrays) -> None:
        """Count one collective + its per-rank payload bytes into
        ``comms.ops`` / ``comms.bytes`` labeled ``{op=...,axis=...}``.
        Runs at trace time from static shape/dtype only — once per jit
        trace (the obs.count_dispatch semantics), zero host syncs, one
        flag check when observability is off. The sanitize lane's
        collective-schedule recorder taps the same per-trace event.

        Every collective is also a named fault point
        (``comms.<verb>``, robust.faults): a fault plan can fail a
        collective *at trace time* — aborting the trace exactly where a
        wedged ICI link would abort the program — so distributed
        failure handling is CI-testable without breaking hardware.

        Multi-axis communicators attribute PER AXIS (ISSUE 19): a
        collective over ``("dcn", "ici")`` lowers to one stage per
        axis (inner reduce/gather, then outer over the inner result),
        so it counts one op on each constituent axis — ``axis=ici`` and
        ``axis=dcn`` series, never a lumped ``dcn+ici`` label. Fixed-
        size verbs charge each stage the per-rank payload; gather-
        family verbs charge each stage its materialized table (the
        inner stage gathers size(inner)×payload, the outer stage
        size(outer)× that) — the hierarchical-schedule byte model that
        lets the scoreboard separate cheap-ICI from scarce-DCN traffic.
        The sanitize-lane schedule recorder keeps the joined label (one
        collective, one schedule slot)."""
        _faults.faultpoint(f"comms.{op_name}")
        recording = _sanitize.comms_schedule_recording()
        counting = _obs.enabled()
        if not (recording or counting):
            return
        payload = _payload_bytes(*arrays)
        nbytes = payload
        if op_name in _GATHER_FAMILY:
            # the materialized gathered table (axis size is static at
            # trace time — same int() the ring perms rely on)
            nbytes *= int(_axis_size(self.axis_name))
        if recording:
            _sanitize.note_collective(op_name,
                                      _axis_label(self.axis_name), nbytes)
        if not counting:
            return
        # host identity (ISSUE 15): in a launcher-ranked pod process
        # (RAFT_TPU_RANK set) every comms series carries the host's
        # rank, so per-host flight/JSONL dumps merged by obs.fleet
        # attribute collective traffic to the process that issued it.
        # One extra label per process (its own rank) — cardinality 1.
        rank = _fleet.rank()
        reg = _obs.registry()
        if isinstance(self.axis_name, str):
            per_axis = [(self.axis_name, nbytes)]
        else:
            per_axis = []
            mult = 1
            # innermost stage first: its gathered table is what the
            # next (outer) stage's gather moves
            for a in reversed(tuple(self.axis_name)):
                if op_name in _GATHER_FAMILY:
                    mult *= int(_axis_size(a))
                    per_axis.append((a, payload * mult))
                else:
                    per_axis.append((a, payload))
        # cost attribution (ISSUE 20): when this trace runs on behalf
        # of a served tenant (the dispatch path brackets searches with
        # neighbors.tiered.serving_tenant), charge the bytes to the
        # tenant per axis. Same trace-time semantics as the series
        # above — static shapes only, zero host syncs (GL01-clean).
        # sys.modules lookup, not an import: build-path traces with no
        # serving layer loaded pay a dict probe, nothing else.
        import sys

        tiered = sys.modules.get("raft_tpu.neighbors.tiered")
        tenant = tiered.current_tenant() if tiered is not None else "-"
        for axis, stage_bytes in per_axis:
            labels = {"op": op_name, "axis": axis}
            if rank is not None:
                labels["rank"] = str(rank)
            reg.inc("comms.ops", 1.0, labels=labels)
            reg.inc("comms.bytes", float(stage_bytes), labels=labels)
            if tenant != "-":
                reg.inc("cost.comms_bytes", float(stage_bytes),
                        labels={"tenant": tenant, "axis": axis})

    # -- collectives -------------------------------------------------------
    def _allreduce_raw(self, x, op: Op):
        if op == Op.PROD:
            return jnp.exp(lax.psum(jnp.log(x), self.axis_name))  # rarely used
        return _REDUCERS[op](x, self.axis_name)

    def allreduce(self, x, op: Op = Op.SUM):
        """reference: comms_t::allreduce (core/comms.hpp:344)."""
        self._count("allreduce", x)
        with _annotate("raft_tpu.comms.allreduce"):
            return self._allreduce_raw(x, op)

    def reduce(self, x, root: int = 0, op: Op = Op.SUM):
        """reference: comms_t::reduce — XLA has no rooted reduce; allreduce
        and mask off non-roots (same wire cost on ICI)."""
        self._count("reduce", x)
        with _annotate("raft_tpu.comms.reduce"):
            full = self._allreduce_raw(x, op)
            rank = self.get_rank()
            return jnp.where(rank == root, full, jnp.zeros_like(full))

    def bcast(self, x, root: int = 0):
        """reference: comms_t::bcast — select the root's shard and replicate."""
        self._count("bcast", x)
        with _annotate("raft_tpu.comms.bcast"):
            gathered = lax.all_gather(x, self.axis_name, axis=0)
            return gathered[root]

    def allgather(self, x, axis: int = 0, tiled: bool = False):
        """reference: comms_t::allgather."""
        self._count("allgather", x)
        with _annotate("raft_tpu.comms.allgather"):
            return lax.all_gather(x, self.axis_name, axis=axis, tiled=tiled)

    def gather(self, x, root: int = 0, axis: int = 0):
        """reference: comms_t::gather — SPMD programs have no cheaper rooted
        gather; all ranks hold the result and root semantics are a no-op."""
        self._count("gather", x)
        with _annotate("raft_tpu.comms.gather"):
            return lax.all_gather(x, self.axis_name, axis=axis)

    def _allgatherv_impl(self, x, count, compact: bool):
        counts = lax.all_gather(count, self.axis_name)           # [size]
        g = lax.all_gather(x, self.axis_name, axis=0, tiled=True)
        if not compact:
            return g, counts
        cap = x.shape[0]
        total = g.shape[0]
        local = jnp.arange(total, dtype=jnp.int32) % cap
        rank_of = jnp.arange(total, dtype=jnp.int32) // cap
        invalid = local >= counts[rank_of]
        order = jnp.argsort(invalid, stable=True)  # valid first, rank order
        return jnp.take(g, order, axis=0), counts

    def allgatherv(self, x, count, compact: bool = True):
        """Variable-length allgather (reference: comms_t::allgatherv,
        core/comms.hpp:423-444). Ragged shard sizes are what real
        sharded datasets produce; XLA collectives are statically shaped,
        so each rank contributes a PADDED shard ``x [cap, ...]`` plus
        its valid row ``count``. Returns ``(gathered [size·cap, ...],
        counts [size])`` with every rank's valid rows stable-packed to
        the front in rank order — ``jnp.sum(counts)`` rows are valid,
        the tail is pad. ``compact=False`` skips the packing sort and
        returns the raw padded concatenation (cheaper when the caller
        masks instead of slicing)."""
        self._count("allgatherv", x, count)
        with _annotate("raft_tpu.comms.allgatherv"):
            return self._allgatherv_impl(x, count, compact)

    def gatherv(self, x, count, root: int = 0, compact: bool = True):
        """Variable-length gather (reference: comms_t::gatherv,
        core/comms.hpp:449-470) — rooted semantics are a no-op in SPMD
        (see :meth:`gather`); identical wire cost to allgatherv."""
        self._count("gatherv", x, count)
        with _annotate("raft_tpu.comms.gatherv"):
            return self._allgatherv_impl(x, count, compact)

    def reducescatter(self, x, op: Op = Op.SUM, scatter_dimension: int = 0):
        """reference: comms_t::reducescatter."""
        self._count("reducescatter", x)
        with _annotate("raft_tpu.comms.reducescatter"):
            return lax.psum_scatter(x, self.axis_name,
                                    scatter_dimension=scatter_dimension,
                                    tiled=True)

    def alltoall(self, x, split_axis: int = 0, concat_axis: int = 0):
        """reference: std_comms nccl alltoall (device_multicast analog)."""
        self._count("alltoall", x)
        with _annotate("raft_tpu.comms.alltoall"):
            return lax.all_to_all(x, self.axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    def ppermute(self, x, perm):
        """Point-to-point ring/permute transfer — the structured replacement
        for comms_t::device_send/device_recv pairs (core/comms.hpp:505,531):
        SPMD programs express p2p as a permutation collective."""
        self._count("ppermute", x)
        with _annotate("raft_tpu.comms.ppermute"):
            return lax.ppermute(x, self.axis_name, perm=perm)

    def send_recv_ring(self, x, shift: int = 1):
        """Ring shift by ``shift`` (send to rank+shift, recv from rank-shift).
        Axis sizes are static at trace time, so the permutation is concrete."""
        self._count("send_recv_ring", x)
        with _annotate("raft_tpu.comms.send_recv_ring"):
            size = int(_axis_size(self.axis_name))
            perm = [(i, (i + shift) % size) for i in range(size)]
            return lax.ppermute(x, self.axis_name, perm=perm)

    def ring_topk_hop(self, vals, ids, shift: int = 1):
        """One hop of the ring top-k exchange: the surviving
        ``(vals, ids)`` block moves to rank+``shift`` (recv from
        rank−``shift``). The CPU-mesh / sub-axis fallback of the Pallas
        ``ring_topk_merge`` kernel (``ops/pallas_kernels``) — identical
        schedule, counted identically: one ``comms.ops{op=ring_topk}``
        and one surviving-block ``comms.bytes`` per hop."""
        self._count("ring_topk", vals, ids)
        with _annotate("raft_tpu.comms.ring_topk"):
            size = int(_axis_size(self.axis_name))
            perm = [(i, (i + shift) % size) for i in range(size)]
            return (lax.ppermute(vals, self.axis_name, perm=perm),
                    lax.ppermute(ids, self.axis_name, perm=perm))

    def count_ring_topk(self, n_hops: int, *arrays) -> None:
        """Attribute the Pallas ring kernel's in-kernel exchange to the
        comms telemetry: ``n_hops`` ops and ``n_hops`` surviving-block
        payloads under ``op=ring_topk``, at trace time. The kernel's
        remote DMAs never pass through ``lax``, so without this call
        they would escape ``comms.ops``/``comms.bytes`` — the GL10
        "no collective escapes telemetry" invariant. ``arrays`` carry
        only static shape/dtype (``jax.ShapeDtypeStruct`` works)."""
        for _ in range(int(n_hops)):
            self._count("ring_topk", *arrays)

    def sync_stream(self) -> Status:
        """reference: comms_t::sync_stream (core/comms.hpp:283-290) — XLA
        surfaces collective failure by failing the program; parity no-op."""
        return Status.SUCCESS


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap (reference: raft-dask ``Comms.init``,
    comms.py:172 — NCCL uid exchange over Dask RPC). On TPU this is one
    call into JAX's distributed runtime; no uid plumbing exists."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    jax.distributed.initialize(**kwargs)
