"""Device ms per call of the IVF-Flat search's stages, from the trace.

The search runs one of two programs, chosen by ``ivf_flat.search``
(``ivf_flat._plan``) from the batch: ``_search_impl`` (per query: probe
selection, the gather of each query's probed list blocks, the
contraction and the select) or ``_search_grouped`` (the list-centric
scan over the segment table). Both name their stages with scopes: probe
selection ``raft_tpu.ivf_flat.coarse_quantize``, the per-query gather
``raft_tpu.ivf_flat.gather``, and the rest of the program
``raft_tpu.ivf_flat.scan``.

The map from instruction to stage comes from the executable that ran,
lowered and compiled here from the plan of the window's first call (the
compile caches hand back the same executable), by
``scopes.stage_map``. A stage's device ms per call is the summed
duration of the program's ops in the window under that stage, over the
window's calls; control-flow ops, whose events span their bodies, are
left out. ``other`` is what maps to no stage.

Two guards keep a stale executable from giving a wrong split: the
compiled scopes must be ones the program's code declares (the
persistent cache's key leaves the metadata out, so a cache filled by
code with other scopes hands back other ``op_name`` paths), and every
op the trace names must be an instruction of the executable compiled
here (else the plan is not the one that ran). Either raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import scopes, trace

#: scope name -> stage of the IVF-Flat search programs
STAGES = {"raft_tpu.ivf_flat.coarse_quantize": "quantize",
          "raft_tpu.ivf_flat.gather": "gather",
          "raft_tpu.ivf_flat.scan": "scan"}
#: the programs' names on the trace's ``XLA Modules`` line
PROGRAMS = ("jit__search_impl", "jit__search_grouped")
_PREFIX = "raft_tpu.ivf_flat."


def _compiled(ctx) -> Optional[str]:
    """The optimized HLO text of the search program of the window's
    first call; None where the program declares no stage scopes."""
    from raft_tpu.neighbors import ivf_flat

    search = {key: val for key, val in ctx.config["search"].items()
              if key != "k"}
    program, args, kwargs = ivf_flat._plan(
        ctx.index, ctx.session.batches[0], ctx.config["search"]["k"],
        ivf_flat.SearchParams(**search))
    text = program.lower(*args, **kwargs).compile().as_text()
    own = scopes.declared(ivf_flat, _PREFIX)
    found = scopes.scopes_in(text, _PREFIX)
    if not own and not found:
        return None
    if not found or not found <= own:
        raise RuntimeError(
            f"the IVF-Flat search's compiled scopes {sorted(found)} are "
            f"not the ones its code declares {sorted(own)}: the compile "
            "cache holds an executable built from other code; clear it")
    return text


def stage_ms(ctx) -> Optional[Dict[str, float]]:
    """Device ms per call of each stage and of ``other`` in the traced
    window, with ``program``, the program's own device ms per call (its
    runs on the module line); None where the trace holds no run of a
    search program or the program has no stage scopes. Computed once
    per run and kept on ``ctx``."""
    if not hasattr(ctx, "ivf_flat_stage_ms"):
        ctx.ivf_flat_stage_ms = _stage_ms(ctx)
    return ctx.ivf_flat_stage_ms


def _stage_ms(ctx) -> Optional[Dict[str, float]]:
    calls = len(ctx.window.batches)
    ops = [op for op in ctx.trace.ops if op.module in PROGRAMS]
    if not ops or not calls:
        return None
    text = _compiled(ctx)
    if text is None:
        return None
    stages = scopes.stage_map(text, STAGES)
    unknown = {op.name for op in ops} - set(stages)
    if unknown:
        raise RuntimeError(
            f"{len(unknown)} ops of the traced search program are not in "
            f"the executable compiled for the window's first call (e.g. "
            f"{sorted(unknown)[:3]}): it is not the program that ran")
    lo, hi = ctx.trace.window
    total = {name: 0.0 for name in list(STAGES.values()) + [scopes.OTHER]}
    for op in ops:
        if op.opcode not in trace._CONTAINERS:
            total[stages[op.name]] += max(0.0, min(op.end, hi)
                                          - max(op.start, lo))
    out = {name: 1e3 * secs / calls for name, secs in total.items()}
    out["program"] = 1e3 * ctx.trace.module_seconds(PROGRAMS) / calls
    return out


def read_stage(ctx, stage: str) -> Optional[float]:
    """One stage's device ms per call, or None (``stage_ms``)."""
    ms = stage_ms(ctx)
    return None if ms is None else ms[stage]
