"""Jaxpr-tier rules: lint staged programs without executing them.

``trace`` stages a function with ``jax.make_jaxpr`` (abstract values only
— nothing runs), and the walker descends every sub-jaxpr an equation
carries (``shard_map`` bodies, ``cond`` branches, ``pjit``/``scan``/
``while``/``custom_vjp`` calls), tracking the scope stack so rules know
which mesh axes are bound and which ``cond`` they sit under.

The rules mechanize this repo's prose invariants:

- **APX101** — rank-0 inexact values crossing a ``shard_map``/
  ``shard_over`` boundary of a program the caller declares it will
  differentiate.  jax 0.4.x's old-style shard_map cannot name-check
  rank-0 values crossing the boundary in the transposed program
  (``_check_names`` trips a ``_SpecError`` on scalar residual out-names
  — the exact PR 2 ``dryrun_multichip`` hunt); the repo convention is to
  keep every such scalar ``(1,)``-shaped inside the body and squeeze
  outside (``gpt_parallel_train._local_loss``).
- **APX102** — ``psum``/``ppermute``/... under a ``lax.cond`` branch
  whose predicate is not agreed over the collective's axes.  Ranks that
  disagree on the predicate take different branches and the collective
  deadlocks on real ICI; the sentinel contract (PR 3) requires the
  overflow flag to be ``pmin``-agreed over every axis the guarded
  optimizer communicates on (``resilience/sentinel.py``).
- **APX103** — collectives over axis names absent from the enclosing
  mesh.  Normally jax raises ``NameError: unbound axis name`` at trace
  time — :func:`trace` converts that into this finding — but nested
  scopes and transformed jaxprs can carry the mismatch silently, so the
  static walk checks every collective eqn too.
- **APX104** — malformed ``ppermute`` permutations: duplicate sources,
  duplicate targets (two ranks sending into one receiver — a data race
  that deadlocks a real ring), or indices outside the axis size.  jax
  does NOT validate this at trace time (probed on 0.4.37), and a
  mismatched ring is exactly the failure mode the PR 2 overlap rings
  must never regress into.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

from apex_tpu.analysis.findings import ERROR, WARNING, Finding
from apex_tpu.analysis.registry import register

__all__ = ["trace", "JaxprCtx", "walk", "run_jaxpr_rules"]

# Collective primitives and where their axis names live in eqn.params.
_COLLECTIVE_AXIS_PARAM = {
    "psum": "axes",
    "pmin": "axes",
    "pmax": "axes",
    "ppermute": "axis_name",
    "all_gather": "axis_name",
    "reduce_scatter": "axis_name",
    "all_to_all": "axis_name",
    "axis_index": "axis_name",
    "pbroadcast": "axes",
}
# Collectives that move payload bytes (axis_index only reads the rank).
_TRAFFIC = frozenset(_COLLECTIVE_AXIS_PARAM) - {"axis_index"}
# Reductions that make a value identical on every rank of their axes.
_AGREEMENT = frozenset({"psum", "pmin", "pmax"})


def collective_axes(eqn) -> Tuple[str, ...]:
    """Named mesh axes a collective eqn operates over (positional ints,
    used by some primitives, are not mesh axes and are skipped)."""
    param = _COLLECTIVE_AXIS_PARAM.get(eqn.primitive.name)
    if param is None:
        return ()
    axes = eqn.params.get(param)
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


_ANALYSIS_DIR = __file__.rsplit("/", 1)[0]


def perm_problems(pairs, size: Optional[int] = None) -> List[str]:
    """Why a (source, target) pair list is not a valid partial
    permutation — shared by APX104 (jaxpr ``perm`` params) and APX202
    (HLO ``source_target_pairs``), so the two tiers can never drift."""
    sources = [s for s, _ in pairs]
    targets = [t for _, t in pairs]
    problems = []
    dup_s = sorted({s for s in sources if sources.count(s) > 1})
    dup_t = sorted({t for t in targets if targets.count(t) > 1})
    if dup_s:
        problems.append(f"duplicate sources {dup_s}")
    if dup_t:
        problems.append(f"duplicate targets {dup_t} (two ranks sending "
                        "into one receiver)")
    if size is not None:
        oob = sorted({r for r in sources + targets
                      if r < 0 or r >= size})
        if oob:
            problems.append(f"ranks {oob} outside axis size {size}")
    return problems


def _source(eqn) -> str:
    """Human-readable source location of an eqn (file:line).  The
    analyzer's own tracing frames are skipped so a shard_map staged via
    :func:`trace` reports where the user built it, not where the linter
    called ``make_jaxpr``."""
    try:
        from jax._src import source_info_util

        for frame in source_info_util.user_frames(eqn.source_info):
            if not frame.file_name.startswith(_ANALYSIS_DIR):
                return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return "<unknown source>"


def trace(fn, *args, **kwargs):
    """``jax.make_jaxpr`` without execution.  Returns ``(closed_jaxpr,
    findings)``: an unbound-axis ``NameError`` (a collective over an axis
    the enclosing mesh does not carry — APX103's trace-time form) is
    converted into a finding instead of crashing the lint."""
    import jax

    try:
        return jax.make_jaxpr(fn)(*args, **kwargs), []
    except NameError as e:
        return None, [Finding(
            rule="APX103", severity=ERROR, location=getattr(
                fn, "__name__", str(fn)),
            message=f"tracing failed with unbound axis: {e}",
            remediation="every collective's axis name must be bound by "
                        "the enclosing shard_map/shard_over mesh "
                        "(apex_tpu.parallel.mesh names the canonical "
                        "axes: dcn/dp/pp/cp/tp)")]


# --- the walker ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Scope:
    """One level of the nesting stack above an eqn."""

    kind: str            # "shard_map" | "cond_branch" | "call"
    eqn: Any             # the eqn introducing this scope
    jaxpr: Any           # the jaxpr CONTAINING that eqn
    mesh_axes: Tuple[str, ...] = ()   # shard_map only
    branch_index: int = -1            # cond_branch only


@dataclasses.dataclass(frozen=True)
class Site:
    eqn: Any
    jaxpr: Any                 # jaxpr containing the eqn
    scopes: Tuple[Scope, ...]  # outermost first

    @property
    def mesh_axes(self) -> Tuple[str, ...]:
        """Union of axis names bound by enclosing shard_maps."""
        axes: List[str] = []
        for s in self.scopes:
            if s.kind == "shard_map":
                axes += [a for a in s.mesh_axes if a not in axes]
        return tuple(axes)

    @property
    def in_shard_map(self) -> bool:
        return any(s.kind == "shard_map" for s in self.scopes)

    def shard_map_scope(self) -> Optional[Scope]:
        for s in reversed(self.scopes):
            if s.kind == "shard_map":
                return s
        return None

    def axis_size(self, axes: Sequence[str]) -> Optional[int]:
        """Product of the named axes' sizes on the innermost enclosing
        shard_map mesh (None when unknown)."""
        scope = self.shard_map_scope()
        if scope is None:
            return None
        mesh = scope.eqn.params.get("mesh")
        try:
            shape = dict(mesh.shape)
        except Exception:
            return None
        size = 1
        for a in axes:
            if a not in shape:
                return None
            size *= int(shape[a])
        return size


def _sub_jaxprs(eqn) -> Iterator[Tuple[str, int, Any]]:
    """(param_name, index, open_jaxpr) for every sub-jaxpr in an eqn's
    params — handles both open ``Jaxpr``s (shard_map bodies) and
    ``ClosedJaxpr``s (pjit/scan/cond branches/custom_vjp)."""
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, sub in enumerate(vals):
            if hasattr(sub, "eqns") and hasattr(sub, "invars"):
                yield key, i, sub          # open Jaxpr
            else:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield key, i, inner    # ClosedJaxpr


def walk(closed_jaxpr) -> Iterator[Site]:
    """Yield every eqn at every depth with its scope stack."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def rec(jx, scopes):
        for eqn in jx.eqns:
            yield Site(eqn=eqn, jaxpr=jx, scopes=scopes)
            name = eqn.primitive.name
            for key, i, sub in _sub_jaxprs(eqn):
                if name == "shard_map":
                    mesh = eqn.params.get("mesh")
                    axes = tuple(getattr(mesh, "axis_names", ()))
                    scope = Scope(kind="shard_map", eqn=eqn, jaxpr=jx,
                                  mesh_axes=axes)
                elif name == "cond" and key == "branches":
                    scope = Scope(kind="cond_branch", eqn=eqn, jaxpr=jx,
                                  branch_index=i)
                else:
                    scope = Scope(kind="call", eqn=eqn, jaxpr=jx)
                yield from rec(sub, scopes + (scope,))

    yield from rec(jaxpr, ())


def _collectives_within(jx) -> Iterator[Any]:
    """Every payload-moving collective eqn in ``jx``, at any depth."""
    for site in walk(jx):
        if site.eqn.primitive.name in _TRAFFIC:
            yield site.eqn


def _producers(jx) -> Dict[Any, Any]:
    return {ov: eqn for eqn in jx.eqns for ov in eqn.outvars}


def backward_slice(jx, var):
    """Eqns the value of ``var`` depends on, within ``jx`` only, plus the
    indices of ``jx.invars`` the slice escapes into (``-1`` for consts or
    unknowns) — escapes mean the dependency chain continues in an
    enclosing scope this walk cannot see."""
    from jax._src import core

    producers = _producers(jx)
    invars = list(jx.invars)
    constvars = set(jx.constvars)
    seen: Set[Any] = set()
    eqns: List[Any] = []
    escaped: List[int] = []   # indices into jx.invars (or -1 for consts)
    stack = [var]
    while stack:
        v = stack.pop()
        if isinstance(v, core.Literal) or v in seen:
            continue
        seen.add(v)
        eqn = producers.get(v)
        if eqn is not None:
            eqns.append(eqn)
            stack.extend(eqn.invars)
        elif v in constvars:
            escaped.append(-1)
        else:
            try:
                escaped.append(invars.index(v))
            except ValueError:
                escaped.append(-1)
    return eqns, escaped


def _is_inexact(aval) -> bool:
    import jax.numpy as jnp

    dtype = getattr(aval, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.inexact)


# --- rules ---------------------------------------------------------------


@dataclasses.dataclass
class JaxprCtx:
    """What a jaxpr-tier rule sees."""

    program: Any              # analysis.program.Program
    closed_jaxpr: Any


def run_jaxpr_rules(ctx: JaxprCtx, rules=None) -> List[Finding]:
    from apex_tpu.analysis.registry import rules_for

    findings: List[Finding] = []
    for rule in (rules if rules is not None else rules_for("jaxpr")):
        findings.extend(rule.fn(ctx))
    return findings


@register("APX101", tier="jaxpr", title="rank0-across-shard-map",
          catches="rank-0 inexact value crossing a shard_map boundary "
                  "of a program that will be differentiated",
          motivation="PR 2: old-jax shard_map _SpecError hunt — scalar "
                     "residuals cannot be name-checked in the transposed "
                     "program; keep grad-path scalars (1,)-shaped inside, "
                     "squeeze outside")
def rank0_across_shard_map(ctx: JaxprCtx):
    """Only programs declared ``differentiated`` are checked: a step that
    takes its gradients *inside* the shard_map never transposes the
    boundary, and its scalar loss output is legal on every jax version."""
    from jax._src import core

    if not ctx.program.differentiated:
        return
    for site in walk(ctx.closed_jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "shard_map":
            continue
        sides = (("in", eqn.invars, eqn.params.get("in_specs")),
                 ("out", eqn.outvars, eqn.params.get("out_specs")))
        for side, vars_, names in sides:
            for i, v in enumerate(vars_):
                if side == "in" and isinstance(v, core.Literal):
                    continue  # constants carry no cotangent
                aval = getattr(v, "aval", None)
                if aval is None or getattr(aval, "shape", None) != ():
                    continue
                if not _is_inexact(aval):
                    continue  # integer/bool scalars are not on grad paths
                spec = None
                if names is not None and i < len(names):
                    spec = names[i]
                yield Finding(
                    rule="APX101", severity=ERROR,
                    location=f"{ctx.program.name}: shard_map {side}var "
                             f"[{i}] ({aval.dtype}[], spec={spec}) @ "
                             f"{_source(eqn)}",
                    message="rank-0 inexact value crosses a shard_map "
                            "boundary on a differentiated path; old-jax "
                            "(<=0.4.x) shard_map trips _SpecError "
                            "name-checking scalar residuals in the "
                            "transposed program",
                    remediation="keep the value (1,)-shaped inside the "
                                "shard_map body and squeeze it outside "
                                "(see gpt_parallel_train._local_loss and "
                                "ROADMAP's old-jax constraint)")


@register("APX102", tier="jaxpr", title="collective-under-unagreed-cond",
          catches="collective inside a lax.cond branch whose predicate "
                  "is not agreed over the collective's mesh axes",
          motivation="PR 3: the sentinel's lax.cond-guarded optimizer "
                     "apply — a rank-local overflow flag would diverge "
                     "the branch and deadlock the guarded reduce-"
                     "scatter/all-gather; sentinel_update pmin-agrees it")
def collective_under_unagreed_cond(ctx: JaxprCtx):
    for site in walk(ctx.closed_jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "cond" or not site.in_shard_map:
            continue
        branch_axes: Dict[str, List[str]] = {}
        for bi, branch in enumerate(eqn.params.get("branches", ())):
            inner = getattr(branch, "jaxpr", branch)
            for ceqn in _collectives_within(inner):
                for ax in collective_axes(ceqn):
                    branch_axes.setdefault(ax, []).append(
                        f"branch[{bi}].{ceqn.primitive.name}")
        if not branch_axes:
            continue
        agreed, resolved = _predicate_agreement(site)
        missing = {a: sites for a, sites in branch_axes.items()
                   if a not in agreed}
        if not missing:
            continue
        detail = "; ".join(f"{ax} used by {', '.join(s)}"
                           for ax, s in sorted(missing.items()))
        if resolved:
            yield Finding(
                rule="APX102", severity=ERROR,
                location=f"{ctx.program.name}: cond @ {_source(eqn)}",
                message="collective(s) under lax.cond with a predicate "
                        f"not agreed over their axes ({detail}); ranks "
                        "that disagree take different branches and the "
                        "collective deadlocks",
                remediation="agree the predicate first — "
                            "sentinel_update(axes=...) pmin-reduces the "
                            "finite flag over every axis the guarded "
                            "step communicates on "
                            "(apex_tpu.resilience.sentinel)")
        else:
            yield Finding(
                rule="APX102", severity=WARNING,
                location=f"{ctx.program.name}: cond @ {_source(eqn)}",
                message="collective(s) under lax.cond whose predicate "
                        f"originates outside the analyzable scope "
                        f"({detail} not provably agreed); verify the "
                        "predicate is identical on those ranks",
                remediation="derive the predicate from a pmin/pmax/psum "
                            "over the branch collectives' axes, or pass "
                            "it in fully replicated")


def _predicate_agreement(site: Site) -> Tuple[Set[str], bool]:
    """Axes over which a cond's predicate is provably rank-uniform, and
    whether the dependency slice fully resolved.

    Agreement sources: pmin/pmax/psum reductions in the predicate's
    backward slice (uniform over their axes), and — when the slice
    reaches the enclosing shard_map body's *inputs* — any input whose
    in_specs entry names no mesh axis (fully replicated, hence uniform
    over the whole mesh)."""
    eqn, jx = site.eqn, site.jaxpr
    pred = eqn.invars[0]
    eqns, escaped = backward_slice(jx, pred)
    agreed: Set[str] = set()
    for e in eqns:
        if e.primitive.name in _AGREEMENT:
            agreed.update(collective_axes(e))
    resolved = not escaped
    if escaped:
        scope = site.shard_map_scope()
        # The predicate (partially) comes from outside this jaxpr.  When
        # this jaxpr IS the shard_map body, the body's in_specs say
        # exactly how each escaped input varies: all-replicated inputs
        # are mesh-uniform (agreement over every axis), while a SHARDED
        # input means the predicate provably depends on rank-varying
        # data — the slice is conclusive either way.  Escapes the walk
        # cannot attribute (consts, deeper call scopes) stay unresolved.
        if scope is not None and scope.eqn.params.get("jaxpr") is jx:
            in_specs = scope.eqn.params.get("in_specs", ())
            known = [idx for idx in escaped
                     if 0 <= idx < len(in_specs)]
            if len(known) == len(escaped):
                resolved = True
                # a PartitionSpec whose every entry is None names no axis
                if all(not any(in_specs[idx]) for idx in known):
                    agreed.update(scope.mesh_axes)
    return agreed, resolved


@register("APX103", tier="jaxpr", title="collective-axis-not-in-mesh",
          catches="collective over an axis name the enclosing "
                  "shard_map mesh does not bind",
          motivation="mesh contract (PR 0/1): all code reduces over the "
                     "canonical dcn/dp/pp/cp/tp axes; a collective naming "
                     "an absent axis is a mis-wired reduction group")
def collective_axis_not_in_mesh(ctx: JaxprCtx):
    for site in walk(ctx.closed_jaxpr):
        name = site.eqn.primitive.name
        if name not in _COLLECTIVE_AXIS_PARAM:
            continue
        axes = collective_axes(site.eqn)
        if not axes:
            continue
        bound = site.mesh_axes
        missing = [a for a in axes if a not in bound]
        if not missing:
            continue
        where = ("no enclosing shard_map"
                 if not site.in_shard_map
                 else f"enclosing mesh axes {tuple(bound)}")
        yield Finding(
            rule="APX103", severity=ERROR,
            location=f"{ctx.program.name}: {name} @ {_source(site.eqn)}",
            message=f"collective over axis {missing} but {where}",
            remediation="bind the axis via shard_over on a mesh that "
                        "carries it (initialize_model_parallel always "
                        "names all five canonical axes)")


@register("APX104", tier="jaxpr", title="ppermute-perm-malformed",
          catches="ppermute permutation with duplicate sources/targets "
                  "or out-of-range ranks",
          motivation="PR 2: the overlap rings are chains of ppermute "
                     "hops; a mismatched permutation is a deadlock on "
                     "real ICI, and jax does not validate it at trace "
                     "time")
def ppermute_perm_malformed(ctx: JaxprCtx):
    for site in walk(ctx.closed_jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "ppermute":
            continue
        perm = eqn.params.get("perm", ())
        axes = collective_axes(eqn)
        problems = perm_problems(perm, site.axis_size(axes))
        if not problems:
            continue
        yield Finding(
            rule="APX104", severity=ERROR,
            location=f"{ctx.program.name}: ppermute(axis={axes}) @ "
                     f"{_source(eqn)}",
            message=f"malformed permutation {tuple(perm)}: "
                    + "; ".join(problems),
            remediation="each rank must appear at most once as source "
                        "and once as target; rings use "
                        "[(i, (i±1) % n) for i in range(n)] "
                        "(parallel.collectives.send_recv_next/prev)")
