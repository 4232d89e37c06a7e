"""Typed jaxpr traversal for the dgcver dataflow verifier (layer 3).

The contract suite (layer 2) proves properties of the *lowered text* —
op counts, donation headers, byte identity. Those are sampling checks:
they can say "two all-gathers" but not "the all-gather carries the
selection payload" or "the residual write-back still depends on the
transmit record". This module gives the verifier passes
(:mod:`dgc_tpu.analysis.verify`) a semantic view of the traced program:

* :func:`flatten` — one SSA-style equation list over a ``ClosedJaxpr``
  with every call primitive (pjit / shard_map / scan / cond / while /
  remat / custom_vjp / pallas_call / ...) recursively inlined. Sub-jaxpr
  binders are aliased positionally onto the call equation's operands when
  the arities line up; anything irregular falls back to a conservative
  all-to-all bridge (every output depends on every input), so dataflow
  reachability over-approximates and never under-taints.
* equation provenance — each :class:`FlatEqn` carries the user-frame
  ``file:line (fn)`` from ``eqn.source_info``, so a pass failure names
  the source line that broke the invariant, not a jaxpr index.
* :func:`collectives` — psum/all_gather/... extraction **with axis
  names** (the thing HLO text cannot give: by then axes are replica
  groups).
* :func:`tags` — the ``dgcver.*`` dataflow anchors the engine plants via
  :func:`dgc_tpu.ops.kernels.vtag` (``checkpoint_name`` identity
  primitives: visible in the jaxpr, zero ops in lowered HLO).
* :func:`forward_taint` — fixpoint forward reachability with an optional
  per-equation propagation predicate (the dtype-flow pass uses it to
  track a *narrow-typed* value only until it is re-widened).
* :func:`peak_live_bytes` — linear-scan liveness estimate over the
  equation list (the donation pass's report metric).

Everything here is pure traversal over ``jax.make_jaxpr`` output — no
compilation, so a full verify sweep stays inside the t1 wall-clock
budget.
"""

import contextlib
from dataclasses import dataclass, field
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Set, Tuple)

__all__ = [
    "FlatEqn", "FlatProgram", "CollectiveSite", "flatten", "collectives",
    "tags", "forward_taint", "peak_live_bytes", "aval_bytes",
    "COLLECTIVE_PRIMS",
]

#: jaxpr-level cross-worker collective primitives. ``pmean`` never
#: appears — it lowers to psum + div before the jaxpr is built.
COLLECTIVE_PRIMS = frozenset({
    "psum", "all_gather", "all_to_all", "ppermute", "pmax", "pmin",
    "reduce_scatter", "psum_scatter", "pgather",
})

#: primitives whose sub-jaxpr binders map 1:1 onto the call equation's
#: operands/results when the arities match (the common case for pjit,
#: closed_call, remat, custom_* and shard_map)
_POSITIONAL_OK = frozenset({
    "pjit", "closed_call", "core_call", "xla_call", "remat", "remat2",
    "checkpoint", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call", "custom_jvp_call_jaxpr", "shard_map", "scan",
})


@dataclass(frozen=True)
class FlatEqn:
    """One inlined equation: primitive name, global var ids, params,
    provenance. ``invars``/``outvars`` are ids into the owning
    :class:`FlatProgram`'s value space (literals are dropped)."""
    prim: str
    invars: Tuple[int, ...]
    outvars: Tuple[int, ...]
    params: Dict
    source: str          # "path/to/file.py:123 (fn_name)" or ""
    depth: int           # call-nesting depth (0 = top level)
    #: equation lives inside a pallas_call body: its outputs are VMEM
    #: scratch / block refs, not HBM allocations — liveness accounting
    #: skips them (the kernel's HBM traffic is the call's own operands)
    vmem: bool = False


@dataclass
class FlatProgram:
    """Flattened view of a ClosedJaxpr: SSA equation list + avals."""
    eqns: List[FlatEqn] = field(default_factory=list)
    invars: Tuple[int, ...] = ()      # top-level inputs, in order
    outvars: Tuple[int, ...] = ()     # top-level outputs, in order
    avals: Dict[int, object] = field(default_factory=dict)

    def producers(self) -> Dict[int, List[FlatEqn]]:
        out: Dict[int, List[FlatEqn]] = {}
        for e in self.eqns:
            for v in e.outvars:
                out.setdefault(v, []).append(e)
        return out


@dataclass(frozen=True)
class CollectiveSite:
    """One collective equation with its named mesh axes."""
    prim: str
    axes: Tuple[str, ...]
    source: str
    eqn_index: int


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        if eqn.primitive.name == "name":
            # dgcver anchors are planted through kernels.vtag — the
            # actionable site is the CALLER (where the tag lives), not
            # the helper's own checkpoint_name line
            for fr in source_info_util.user_frames(
                    eqn.source_info.traceback):
                fn = fr.file_name.replace("\\", "/")
                # function_name is the qualified name on this Python
                # ("vtag.<locals>.leaf")
                if not (fn.endswith("dgc_tpu/ops/kernels.py")
                        and fr.function_name.rsplit(".", 1)[-1]
                        in ("vtag", "leaf")):
                    return (f"{fr.file_name}:{fr.start_line} "
                            f"({fr.function_name})")
        return str(source_info_util.summarize(eqn.source_info))
    except Exception:
        return ""


def _sub_jaxprs(params: Dict) -> List[Tuple[str, object]]:
    """(param_name, jaxpr-like) pairs inside an equation's params."""
    from jax._src import core
    out = []
    for k, v in params.items():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            if isinstance(item, (core.Jaxpr, core.ClosedJaxpr)):
                out.append((k, item))
    return out


def _open(jx):
    """(jaxpr, consts) from either Jaxpr or ClosedJaxpr."""
    if hasattr(jx, "jaxpr"):
        return jx.jaxpr, list(getattr(jx, "consts", []) or [])
    return jx, []


class _Flattener:
    def __init__(self):
        self.prog = FlatProgram()
        self._next = 0
        #: id(Var) -> global id, for the jaxpr being walked. One map per
        #: INLINING of a sub-jaxpr (see :meth:`_scope`): JAX caches the
        #: traced jaxpr of a jitted helper (``x.at[i].add`` is one since
        #: 0.5), so two call sites share one Jaxpr object and its Var
        #: objects — a single global map would merge their dataflow and
        #: let taint cross between unrelated calls
        self._ids: Dict[int, int] = {}

    def _gid(self, var) -> Optional[int]:
        from jax._src import core
        if isinstance(var, core.Literal):
            return None
        key = id(var)
        if key not in self._ids:
            self._ids[key] = self._next
            self.prog.avals[self._next] = getattr(var, "aval", None)
            self._next += 1
        return self._ids[key]

    def _alias(self, var, gid: int) -> None:
        """Bind a sub-jaxpr binder var to an existing global id."""
        from jax._src import core
        if isinstance(var, core.Literal) or gid is None:
            return
        self._ids[id(var)] = gid
        if self.prog.avals.get(gid) is None:
            self.prog.avals[gid] = getattr(var, "aval", None)

    @contextlib.contextmanager
    def _scope(self):
        """A fresh id map for one inlining of a sub-jaxpr. Jaxprs are
        closed — a body names only its own binders, constvars and
        locals — so nothing of the caller's map is needed inside; the
        caller binds the binders (:meth:`_alias` / :meth:`_fresh`)
        after entering."""
        outer, self._ids = self._ids, {}
        try:
            yield
        finally:
            self._ids = outer

    def _fresh(self, var) -> int:
        gid = self._next
        self._next += 1
        self._ids[id(var)] = gid
        self.prog.avals[gid] = getattr(var, "aval", None)
        return gid

    def run(self, closed) -> FlatProgram:
        jaxpr, _ = _open(closed)
        self.prog.invars = tuple(self._gid(v) for v in jaxpr.invars)
        self._walk(closed, depth=0, vmem=False)
        self.prog.outvars = tuple(
            g for g in (self._gid(v) for v in jaxpr.outvars)
            if g is not None)
        return self.prog

    # -- core recursion --------------------------------------------------
    def _walk(self, closed, depth: int, vmem: bool = False) -> None:
        jaxpr, _ = _open(closed)
        for cv in jaxpr.constvars:
            self._gid(cv)
        for eqn in jaxpr.eqns:
            subs = _sub_jaxprs(eqn.params)
            name = eqn.primitive.name
            ins = tuple(g for g in (self._gid(v) for v in eqn.invars)
                        if g is not None)
            src = _source_of(eqn)
            if not subs:
                outs = tuple(self._gid(v) for v in eqn.outvars)
                self.prog.eqns.append(FlatEqn(
                    name, ins, outs, dict(eqn.params), src, depth, vmem))
                continue
            self._inline(eqn, name, ins, src, subs, depth, vmem)

    def _inline(self, eqn, name, ins, src, subs, depth,
                vmem: bool = False) -> None:
        """Inline one call equation. Records a marker FlatEqn for the
        call itself (no dataflow — the sub-jaxpr carries it), or a
        bridge FlatEqn (full dataflow) when binders can't be aliased."""
        in_gids = [self._gid(v) for v in eqn.invars]

        positional = False
        if len(subs) == 1 and name in _POSITIONAL_OK:
            sub_jaxpr, _ = _open(subs[0][1])
            positional = len(sub_jaxpr.invars) == len(eqn.invars)
        if name == "cond" and subs:
            # invars[0] is the branch index; the rest map onto every
            # branch's binders
            positional = all(
                len(_open(s)[0].invars) == len(eqn.invars) - 1
                for _, s in subs)

        if positional and name == "cond":
            # every branch writes the same call outputs: alias the call
            # outvars to each branch's outvars via a join eqn
            join_ins: List[int] = []
            for _, sub in subs:
                sj, _ = _open(sub)
                with self._scope():
                    for bv, gid in zip(sj.invars, in_gids[1:]):
                        self._alias(bv, gid)
                    self._walk(sub, depth + 1, vmem)
                    join_ins.extend(
                        g for g in (self._gid(v) for v in sj.outvars)
                        if g is not None)
            out_gids = tuple(self._gid(v) for v in eqn.outvars)
            self.prog.eqns.append(FlatEqn(
                f"{name}[join]", tuple(join_ins), out_gids,
                {}, src, depth, vmem))
            return

        if positional:
            _, sub = subs[0]
            sj, _ = _open(sub)
            with self._scope():
                for bv, gid in zip(sj.invars, in_gids):
                    self._alias(bv, gid)
                self._walk(sub, depth + 1, vmem)
                sub_outs = tuple(
                    g for g in (self._gid(v) for v in sj.outvars)
                    if g is not None)
            out_gids = tuple(self._gid(v) for v in eqn.outvars)
            # scan's ys outputs are stacked copies of the body outs; a
            # join eqn keeps the dependency without claiming identity
            self.prog.eqns.append(FlatEqn(
                f"{name}[join]", sub_outs, out_gids, {}, src, depth, vmem))
            return

        # irregular arity (while, pallas_call, unknown callers): walk
        # sub-jaxprs with fresh binders bridged all-to-all — reachability
        # over-approximates, collectives inside are still found. Inside a
        # pallas_call body every binder is a VMEM block ref or scratch —
        # the bind eqn (which defines the fresh binders) and the whole
        # sub-walk carry vmem=True so liveness accounting skips them;
        # the join eqn defines the call's real HBM outputs at caller scope
        sub_vmem = vmem or name == "pallas_call"
        bridge_outs: List[int] = []
        for _, sub in subs:
            sj, _ = _open(sub)
            with self._scope():
                fresh_ins = tuple(self._fresh(v) for v in sj.invars)
                self.prog.eqns.append(FlatEqn(
                    f"{name}[bind]", ins, fresh_ins, {}, src, depth,
                    sub_vmem))
                self._walk(sub, depth + 1, sub_vmem)
                bridge_outs.extend(
                    g for g in (self._gid(v) for v in sj.outvars)
                    if g is not None)
        out_gids = tuple(self._gid(v) for v in eqn.outvars)
        self.prog.eqns.append(FlatEqn(
            f"{name}[join]", tuple(ins) + tuple(bridge_outs), out_gids,
            {}, src, depth, vmem))


def flatten(closed) -> FlatProgram:
    """Flatten a ``ClosedJaxpr`` (from ``jax.make_jaxpr``) into one
    equation list with call primitives inlined."""
    return _Flattener().run(closed)


def _axis_names(params: Dict) -> Tuple[str, ...]:
    names: List[str] = []
    for key in ("axes", "axis_name", "axis", "axis_names"):
        v = params.get(key)
        if v is None:
            continue
        items = v if isinstance(v, (tuple, list)) else (v,)
        names.extend(str(a) for a in items if isinstance(a, str))
    return tuple(names)


def collectives(prog: FlatProgram) -> List[CollectiveSite]:
    """Every collective equation with its named mesh axes, in program
    order. Positional (int) axes — vmapped collectives — are dropped
    from ``axes``; a site with no named axis still appears (empty
    tuple), so the audit can flag it."""
    out: List[CollectiveSite] = []
    for i, e in enumerate(prog.eqns):
        if e.prim in COLLECTIVE_PRIMS:
            out.append(CollectiveSite(e.prim, _axis_names(e.params),
                                      e.source, i))
    return out


def tags(prog: FlatProgram) -> Dict[str, List[FlatEqn]]:
    """``checkpoint_name`` anchor equations by tag name. The engine's
    anchors all use the ``dgcver.`` prefix (see ``kernels.vtag``)."""
    out: Dict[str, List[FlatEqn]] = {}
    for e in prog.eqns:
        if e.prim == "name":
            out.setdefault(str(e.params.get("name", "")), []).append(e)
    return out


def forward_taint(prog: FlatProgram, seeds: Iterable[int],
                  through: Optional[Callable[[FlatEqn], bool]] = None,
                  ) -> Set[int]:
    """Fixpoint forward reachability from ``seeds`` (global var ids).

    ``through(eqn)`` — when given, an equation only propagates taint
    from its inputs to its outputs if the predicate holds (the dtype-flow
    pass stops narrow-taint at re-widening converts). Seeds are always
    in the result. Fixpoint iteration handles the back-edges introduced
    by while-loop bridge equations."""
    tainted: Set[int] = set(seeds)
    changed = True
    while changed:
        changed = False
        for e in prog.eqns:
            if through is not None and not through(e):
                continue
            if any(v in tainted for v in e.invars):
                for v in e.outvars:
                    if v is not None and v not in tainted:
                        tainted.add(v)
                        changed = True
    return tainted


def aval_bytes(aval) -> int:
    """Byte size of a ShapedArray-like aval (0 for abstract tokens)."""
    try:
        import numpy as np
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            return 0
        return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    except Exception:
        return 0


def peak_live_bytes(prog: FlatProgram) -> int:
    """Linear-scan liveness estimate over the flattened equation list.

    An upper-bound *estimate* of resident bytes under the jaxpr's
    program order: inputs are live from entry, every value stays live
    until its last textual use, outputs stay live to the end. XLA's
    scheduler and fusions will do better; the point is a stable,
    config-comparable number the regression gate can watch — a doubled
    peak means a donation or an accidental full-buffer copy went
    missing, whatever the compiler then salvages.

    Values defined INSIDE a pallas_call body (``FlatEqn.vmem``) are
    block refs and VMEM scratch, not HBM allocations — they are
    excluded, so a fused-kernel build is compared on the same HBM
    footing as the staged XLA build it replaces (the kernel's real HBM
    traffic is the call's own operands, which stay counted)."""
    onchip: Set[int] = {v for e in prog.eqns if e.vmem
                        for v in e.outvars if v is not None}

    def _bytes(v) -> int:
        if v in onchip:
            return 0
        return aval_bytes(prog.avals.get(v))

    last_use: Dict[int, int] = {}
    for i, e in enumerate(prog.eqns):
        for v in e.invars:
            last_use[v] = i
    n = len(prog.eqns)
    for v in prog.outvars:
        last_use[v] = n
    live: Set[int] = set(prog.invars)
    peak = cur = sum(_bytes(v) for v in live)
    for i, e in enumerate(prog.eqns):
        for v in e.outvars:
            if v is not None and v not in live:
                live.add(v)
                cur += _bytes(v)
        peak = max(peak, cur)
        for v in set(e.invars) | set(e.outvars):
            if v in live and last_use.get(v, -1) <= i:
                live.discard(v)
                cur -= _bytes(v)
    return int(peak)
