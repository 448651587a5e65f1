"""Static ProgramProfile extraction: lower a solver program, never run it.

Two complementary views of the same program feed the auditor:

* the **jaxpr walk** (`profile_fn` / `profile_jaxpr`) recurses through
  every sub-jaxpr (pjit bodies, shard_map regions, scan/while/cond
  branches) and produces *static* counts: a ``scan`` with a Python-static
  ``length`` multiplies everything inside it, so a collective inside a
  ``fori_loop`` over panels counts once per panel — exactly the number the
  paper-level contracts are written in ("3 collectives per panel",
  "1 + iters all_gathers");
* the **StableHLO text** (`hlo_counts`) counts each op once per loop
  *body* — the view PR 6's hand-grepped assertions used — kept as a
  cross-reference and because some structure (``custom_call`` targets)
  only exists post-lowering.

Nothing here executes device code: ``jax.make_jaxpr`` and ``.lower()``
trace with abstract values, so the audit of a 2-device mesh program runs
fine on forced host devices in CI.

Counting semantics worth pinning down:

* ``cond`` branches are **summed** — a collective present in either branch
  counts. This is a deliberate upper bound: the KE segment guards its
  block step behind ``lax.cond(j >= j0)`` and the contract must hold for
  the branch that communicates.
* ``while`` loops with traced bounds have no static trip count; their
  bodies count **once** and the loop is reported in ``dynamic_whiles`` so
  a contract can cap how many dynamic loops a program is allowed.
* ``scan`` respects ``unroll``: effective sequential steps are
  ``ceil(length / unroll)`` — the quantity ``variant_model`` prices as
  ``loop_steps`` (the unroll is the fused TT3 path's whole speedup).
* ``pallas_call`` bodies are *not* recursed into for the op counts (they
  are device kernels, not HLO); their grid/BlockSpec structure is captured
  in ``pallas_calls`` for the kernel lint.
"""
from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

# jaxpr primitive name -> canonical collective kind (the HLO-level name)
COLLECTIVE_KINDS: Dict[str, str] = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
    "ppermute": "collective_permute",
    "pshuffle": "collective_permute",
    "all_to_all": "all_to_all",
}

#: StableHLO ops counted in the lowered text (once per loop body).
HLO_OPS: Tuple[str, ...] = (
    "stablehlo.all_reduce", "stablehlo.all_gather",
    "stablehlo.reduce_scatter", "stablehlo.collective_permute",
    "stablehlo.all_to_all", "stablehlo.while", "stablehlo.custom_call",
    "stablehlo.dynamic_slice", "stablehlo.convert",
)

_DOWNCAST_TARGETS = ("float32", "bfloat16", "float16")


@dataclasses.dataclass
class CollectiveSite:
    """One collective equation, with its static (loop-multiplied) count."""
    kind: str               # all_reduce / all_gather / ...
    primitive: str          # the jaxpr primitive (psum, all_gather, ...)
    shape: Tuple[int, ...]
    dtype: str
    bytes_per_call: int
    static_count: int       # times this site executes per program dispatch

    def as_json_dict(self) -> dict:
        return {"kind": self.kind, "primitive": self.primitive,
                "shape": list(self.shape), "dtype": self.dtype,
                "bytes_per_call": self.bytes_per_call,
                "static_count": self.static_count}


@dataclasses.dataclass
class LoopInfo:
    """One scan/while equation (a fori_loop lowers to one of these)."""
    kind: str                       # "scan" | "while"
    length: Optional[int]           # static trip count (None for while)
    unroll: int
    steps: Optional[int]            # ceil(length/unroll) * outer multiplier
    collectives_per_trip: int       # collectives one trip executes
    depth: int                      # loop nesting depth (0 = top level)

    def as_json_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PallasCallInfo:
    name: str
    grid: Tuple[int, ...]
    block_shapes: Tuple[Tuple[int, ...], ...]
    static_count: int
    vmem_bytes_estimate: int        # sum of blocks x itemsize x 2 (dbl-buf)

    def as_json_dict(self) -> dict:
        return {"name": self.name, "grid": list(self.grid),
                "block_shapes": [list(b) for b in self.block_shapes],
                "static_count": self.static_count,
                "vmem_bytes_estimate": self.vmem_bytes_estimate}


@dataclasses.dataclass
class ProgramProfile:
    name: str
    primitive_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    collectives: List[CollectiveSite] = dataclasses.field(default_factory=list)
    loops: List[LoopInfo] = dataclasses.field(default_factory=list)
    pallas_calls: List[PallasCallInfo] = dataclasses.field(default_factory=list)
    converts: Dict[str, int] = dataclasses.field(default_factory=dict)
    input_dtypes: List[str] = dataclasses.field(default_factory=list)
    output_dtypes: List[str] = dataclasses.field(default_factory=list)
    weak_type_inputs: int = 0
    dynamic_whiles: int = 0
    dynamic_slices: int = 0
    gathers: int = 0
    callbacks: int = 0
    loop_steps_static: int = 0
    hlo_counts: Optional[Dict[str, int]] = None

    # ---- derived views ---------------------------------------------------
    def collective_counts(self) -> Dict[str, int]:
        c: Counter = Counter()
        for site in self.collectives:
            c[site.kind] += site.static_count
        return dict(c)

    def total_collectives(self) -> int:
        return sum(s.static_count for s in self.collectives)

    def collective_bytes(self) -> int:
        return sum(s.bytes_per_call * s.static_count
                   for s in self.collectives)

    def max_collectives_per_loop_trip(self) -> int:
        """Collectives a single trip of the busiest loop executes — the
        'per block step' / 'per panel' number the contracts are written in.
        """
        return max((lp.collectives_per_trip for lp in self.loops), default=0)

    def f64_downcasts(self) -> Dict[str, int]:
        """convert_element_type sites demoting float64 — precision leaks."""
        return {k: v for k, v in self.converts.items()
                if k.startswith("float64->")
                and k.split("->")[1] in _DOWNCAST_TARGETS}

    def dtypes_seen(self) -> List[str]:
        seen = set(self.input_dtypes) | set(self.output_dtypes)
        for k in self.converts:
            seen.update(k.split("->"))
        return sorted(seen)

    def as_json_dict(self) -> dict:
        return {
            "name": self.name,
            "collective_counts": self.collective_counts(),
            "total_collectives": self.total_collectives(),
            "collective_bytes": self.collective_bytes(),
            "max_collectives_per_loop_trip":
                self.max_collectives_per_loop_trip(),
            "collectives": [s.as_json_dict() for s in self.collectives],
            "loops": [lp.as_json_dict() for lp in self.loops],
            "loop_steps_static": self.loop_steps_static,
            "dynamic_whiles": self.dynamic_whiles,
            "dynamic_slices": self.dynamic_slices,
            "gathers": self.gathers,
            "callbacks": self.callbacks,
            "pallas_calls": [p.as_json_dict() for p in self.pallas_calls],
            "converts": dict(self.converts),
            "f64_downcasts": self.f64_downcasts(),
            "input_dtypes": self.input_dtypes,
            "output_dtypes": self.output_dtypes,
            "weak_type_inputs": self.weak_type_inputs,
            "dtypes_seen": self.dtypes_seen(),
            "hlo_counts": self.hlo_counts,
        }


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _subjaxprs(eqn):
    """Every jaxpr reachable from an equation's params.

    pjit/scan/while store ClosedJaxpr; shard_map stores a bare Jaxpr;
    cond stores a list of branches — yield them all.
    """
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for sub in vs:
            if isinstance(sub, ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, Jaxpr):
                yield sub


def _aval_bytes(aval) -> int:
    try:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _scan_length(eqn) -> Tuple[int, int]:
    length = int(eqn.params.get("length", 0) or 0)
    unroll = eqn.params.get("unroll", 1)
    unroll = int(unroll) if isinstance(unroll, int) and unroll else 1
    return length, max(unroll, 1)


def _count_body_collectives(jx: Jaxpr) -> int:
    """Collectives ONE trip of a loop body executes (nested loops
    multiplied by their static lengths; cond branches summed)."""
    total = 0
    for eqn in jx.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_KINDS:
            total += 1
        if name == "pallas_call":
            continue
        mult = 1
        if name == "scan":
            length, _ = _scan_length(eqn)
            mult = max(length, 1)
        for sub in _subjaxprs(eqn):
            total += mult * _count_body_collectives(sub)
    return total


def _pallas_info(eqn, mult: int) -> PallasCallInfo:
    name = str(eqn.params.get("name", "")) or "pallas_call"
    grid: Tuple[int, ...] = ()
    blocks: List[Tuple[int, ...]] = []
    vmem = 0
    gm = eqn.params.get("grid_mapping")
    if gm is not None:
        try:
            grid = tuple(int(g) for g in gm.grid)
        except Exception:
            grid = ()
        for bm in getattr(gm, "block_mappings", ()) or ():
            if bm is None:
                continue
            shape = tuple(int(d) for d in getattr(bm, "block_shape", ())
                          if isinstance(d, int))
            if shape:
                blocks.append(shape)
                # double-buffered block residency, fp32 floor of 4 B/elt —
                # refined per-dtype by the kernel lint when avals are known
                vmem += int(math.prod(shape)) * 4 * 2
    return PallasCallInfo(name=name, grid=grid, block_shapes=tuple(blocks),
                          static_count=mult, vmem_bytes_estimate=vmem)


def _walk(jx: Jaxpr, mult: int, depth: int, prof: ProgramProfile) -> None:
    for eqn in jx.eqns:
        name = eqn.primitive.name
        prof.primitive_counts[name] = (
            prof.primitive_counts.get(name, 0) + mult)
        if name in COLLECTIVE_KINDS:
            out = eqn.outvars[0].aval
            prof.collectives.append(CollectiveSite(
                kind=COLLECTIVE_KINDS[name], primitive=name,
                shape=tuple(int(d) for d in out.shape),
                dtype=str(out.dtype), bytes_per_call=_aval_bytes(out),
                static_count=mult))
        elif name == "convert_element_type":
            src = str(eqn.invars[0].aval.dtype)
            dst = str(eqn.params.get("new_dtype"))
            key = f"{src}->{dst}"
            prof.converts[key] = prof.converts.get(key, 0) + mult
        elif name in ("dynamic_slice", "dynamic_update_slice"):
            prof.dynamic_slices += mult
        elif name == "gather":
            prof.gathers += mult
        elif "callback" in name:
            prof.callbacks += mult
        elif name == "pallas_call":
            prof.pallas_calls.append(_pallas_info(eqn, mult))
            continue                       # device kernel: don't recurse

        inner = mult
        if name == "scan":
            length, unroll = _scan_length(eqn)
            steps = math.ceil(length / unroll) if length else 0
            body_coll = sum(_count_body_collectives(s)
                            for s in _subjaxprs(eqn))
            prof.loops.append(LoopInfo(
                kind="scan", length=length, unroll=unroll,
                steps=steps * mult, collectives_per_trip=body_coll,
                depth=depth))
            prof.loop_steps_static += steps * mult
            inner = mult * max(length, 1)
            depth_inner = depth + 1
        elif name == "while":
            body_coll = sum(_count_body_collectives(s)
                            for s in _subjaxprs(eqn))
            prof.loops.append(LoopInfo(
                kind="while", length=None, unroll=1, steps=None,
                collectives_per_trip=body_coll, depth=depth))
            prof.dynamic_whiles += mult
            depth_inner = depth + 1
        else:
            depth_inner = depth + 1 if name == "cond" else depth
        for sub in _subjaxprs(eqn):
            _walk(sub, inner, depth_inner, prof)


def profile_jaxpr(closed: ClosedJaxpr, name: str = "") -> ProgramProfile:
    prof = ProgramProfile(name=name)
    jx = closed.jaxpr
    prof.input_dtypes = [str(v.aval.dtype) for v in jx.invars
                         if hasattr(v.aval, "dtype")]
    prof.output_dtypes = [str(v.aval.dtype) for v in jx.outvars
                          if hasattr(v.aval, "dtype")]
    prof.weak_type_inputs = sum(
        1 for v in jx.invars if getattr(v.aval, "weak_type", False))
    _walk(jx, 1, 0, prof)
    return prof


def hlo_counts(text: str) -> Dict[str, int]:
    """Occurrences of each audited StableHLO op in lowered module text
    (once per loop body — the PR-6-era grep view, kept for cross-ref)."""
    return {op: text.count(op) for op in HLO_OPS}


def profile_fn(fn: Callable, *args: Any, name: str = "",
               with_hlo: bool = True, **kwargs: Any) -> ProgramProfile:
    """Lower ``fn`` on abstract args (ShapeDtypeStructs work) — never run it.

    ``fn`` may be a plain traceable callable or an already-jitted program;
    the jaxpr walk uses ``jax.make_jaxpr`` either way, and the StableHLO
    view uses ``fn.lower`` when available (falling back to ``jax.jit``).
    ``kwargs`` are treated as *static* (bound before tracing, so a jitted
    fn's ``static_argnames`` stay hashable); array operands go in ``args``.
    """
    import functools
    trace_fn = functools.partial(fn, **kwargs) if kwargs else fn
    closed = jax.make_jaxpr(trace_fn)(*args)
    prof = profile_jaxpr(closed, name=name or getattr(fn, "__name__", "fn"))
    if with_hlo:
        lower = getattr(fn, "lower", None)
        if lower is None:
            lower = jax.jit(fn).lower
        prof.hlo_counts = hlo_counts(lower(*args, **kwargs).as_text())
    return prof


__all__ = ["ProgramProfile", "CollectiveSite", "LoopInfo", "PallasCallInfo",
           "profile_fn", "profile_jaxpr", "hlo_counts", "COLLECTIVE_KINDS",
           "HLO_OPS"]
