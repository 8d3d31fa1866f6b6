"""Lint core: findings, policies, the pass registry, and the jaxpr walk.

A *pass* is a function ``(LintContext) -> list[Finding]`` registered
under a stable name. A *context* is one traced entry point — its closed
jaxpr, its flat input record (names, avals, declared donation), the
lowered StableHLO text when the entry was lowered, and the
:class:`LintPolicy` describing which invariants apply there. Policies
exist because the same eqn is correct in one program and a bug in
another: a float psum over ``tp`` is the Megatron activation reduction
inside a train step and a quantization escape inside the int8 collective
— only the policy knows which program it is looking at.

Everything here is trace-time only by default: no device execution, no
compile. The compiled-HLO plane (analysis/hlo.py) is the lazy second
artifact: :attr:`LintContext.hlo` compiles the entry's optimized module
on first read (``lower().compile().as_text()``, CPU-safe) — paid only
when the HLO passes are armed (``lint --hlo``).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np

# Collective primitives and where each keeps its axis names. psum-family
# primitives bind ``axes``; the tiled collectives bind ``axis_name``
# (which may itself be a name or a tuple of names).
_AXES_PARAM = {
    "psum": "axes", "pmax": "axes", "pmin": "axes",
    "reduce_scatter": "axis_name", "all_gather": "axis_name",
    "all_to_all": "axis_name", "ppermute": "axis_name",
    "pbroadcast": "axes", "axis_index": "axis_name",
}
# The subset that moves payload bytes (axis_index is bookkeeping).
COLLECTIVE_PRIMS = frozenset(_AXES_PARAM) - {"axis_index"}
# Phase-1 primitives of a two-phase schedule (reduce side) vs phase 2
# (broadcast side): the windowed schedules must keep them paired.
REDUCE_PHASE_PRIMS = frozenset({"reduce_scatter", "all_to_all"})
GATHER_PHASE_PRIMS = frozenset({"all_gather"})
# Primitives that round-trip through the host: reachable from a hot loop
# they serialize the device against Python.
# (jax 0.9.0 lowers jax.debug.print to its own ``debug_print`` primitive;
# jax.debug.callback stays ``debug_callback``.)
HOST_SYNC_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "infeed", "outfeed",
})
# Control-flow primitives whose body re-runs per trip — an eqn inside
# them is "in a hot loop" for the host-sync pass.
LOOP_PRIMS = frozenset({"scan", "while", "fori_loop"})


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint result. ``severity``: "error" (exit-code gating),
    "warning" (reported, non-gating by default), or "info"."""

    pass_name: str
    severity: str
    entrypoint: str
    message: str
    where: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class LintPolicy:
    """Which invariants apply to an entry point.

    ``known_axes``: the enclosing mesh's axis names; any collective
    naming an axis outside this set is an error (empty = meshless entry:
    every named-axis collective is an error).
    ``reduce_axes``: when set, *float-payload* reductions (psum /
    reduce_scatter) must stay on these axes — the grad-sync discipline
    for standalone collective entries. None = don't check (full train
    steps legitimately psum activations over model axes).
    ``expect_two_phase``: reduce-phase and gather-phase collective
    counts must pair per axis (the windowed-schedule invariant: every
    window's reduce-scatter has its all-gather).
    ``expect_swing``: the swing short-cut schedule's invariant — the
    entry must carry exactly this many float-payload ppermute exchange
    steps per reduce axis (log2 of the group size; a dropped exchange
    leaves every rank holding a partial sum, the swing analog of an
    unpaired window). None = not a swing entry, ppermutes unchecked.
    ``expect_hierarchical``: ``(ici_axis, dcn_axis)`` turns on the
    ICI x DCN hybrid invariant (ISSUE 13): the ICI axis carries exactly
    one float-payload reduce-scatter paired with float all-gather(s)
    (the exact fast-plane legs), while the DCN axis moves its payload
    int8-quantized — at least one int8 exchange each direction and NO
    float-payload reduction over it (scales ride f32, values never do).
    A refactor that loses the compression re-routes the full payload
    over the slow plane; one that drops the ICI gather leaves every
    rank a column shard. None = not a hierarchical entry.
    ``wire``: "bf16"/"int8" turn on the wire-dtype discipline (no f32
    payload escapes the compressed wire).
    ``exact_counts``: count/bookkeeping psums must be integer-dtyped
    (the honesty contract: lossy rounds tolerate no rounded counts).
    ``expect_donation``: the entry declares donated args and the
    lowering must actually alias them (the HBM-residency contract).
    ``hot``: the entry runs per step/token — host callbacks anywhere in
    it are findings, not just inside scan/while bodies.
    ``compute_dtype``: "bf16" turns on the upcast lint.
    """

    known_axes: frozenset = frozenset()
    reduce_axes: Optional[frozenset] = None
    expect_two_phase: bool = False
    expect_swing: Optional[int] = None
    expect_hierarchical: Optional[tuple] = None
    wire: Optional[str] = None
    exact_counts: bool = False
    expect_donation: bool = False
    hot: bool = False
    compute_dtype: str = "f32"


@dataclasses.dataclass
class LintContext:
    """One traced entry point, ready for the passes."""

    name: str
    jaxpr: Any  # ClosedJaxpr
    policy: LintPolicy
    # flat input record (post pytree-flatten, same order as lowering):
    arg_names: tuple = ()
    in_avals: tuple = ()
    donated: tuple = ()  # declared donation per flat arg
    stablehlo: Optional[str] = None  # lowered module text, when lowered
    # -- the compiled-HLO second artifact (analysis/hlo.py) ------------
    # which compiled-module invariants apply (hlo.HloPolicy); None =
    # entry opted out of the HLO plane
    hlo_policy: Optional[Any] = None
    # True while the runner will also run the HLO passes over this
    # context — the StableHLO donation pass defers its lowering-
    # survival audit to hlo-aliasing then, so one dropped donation is
    # one finding (with both marker and alias evidence), never two
    hlo_armed: bool = False
    # compiled module text: seeded directly (selfcheck fixtures /
    # golden tests) or produced lazily by the thunk trace_entry stashes
    _hlo_text: Optional[str] = dataclasses.field(
        default=None, repr=False)
    _hlo_thunk: Optional[Callable[[], str]] = dataclasses.field(
        default=None, repr=False)

    @property
    def hlo(self) -> Optional[str]:
        """Optimized HLO text (``lower().compile().as_text()``),
        compiled lazily on first read and cached. None when the entry
        carries neither seeded text nor a compile thunk."""
        if self._hlo_text is None and self._hlo_thunk is not None:
            self._hlo_text = self._hlo_thunk()
        return self._hlo_text


# -- jaxpr traversal ----------------------------------------------------

def _sub_jaxprs(params: dict) -> Iterator[Any]:
    """Yield every Jaxpr nested in an eqn's params (closed or open,
    single or in a branches tuple) — duck-typed so it survives the
    jax.core reshuffles across versions."""
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for item in items:
            if hasattr(item, "eqns"):  # open Jaxpr
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr  # ClosedJaxpr

def iter_eqns(closed_jaxpr, _jaxpr=None, _in_loop=False
              ) -> Iterator[tuple]:
    """Depth-first ``(eqn, in_loop)`` over a closed jaxpr and every
    nested jaxpr (pjit/shard_map/scan/while/cond bodies). ``in_loop`` is
    True for eqns whose enclosing control flow re-runs them per trip."""
    jaxpr = closed_jaxpr.jaxpr if _jaxpr is None else _jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, _in_loop
        inner_loop = _in_loop or eqn.primitive.name in LOOP_PRIMS
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(closed_jaxpr, _jaxpr=sub,
                                 _in_loop=inner_loop)


def eqn_axes(eqn) -> tuple:
    """The axis names a collective eqn binds, flattened to a tuple of
    strings (handles both the ``axes`` and ``axis_name`` spellings and
    the name-or-tuple convention)."""
    param = _AXES_PARAM.get(eqn.primitive.name)
    if param is None:
        return ()
    v = eqn.params.get(param)
    if v is None:
        return ()
    names = v if isinstance(v, (list, tuple)) else (v,)
    return tuple(str(n) for n in names)


def out_elems(eqn) -> int:
    """Total output elements of an eqn (payload-size proxy)."""
    total = 0
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", ())
        total += int(np.prod(shape)) if shape else 1
    return total


def out_dtype(eqn):
    """Dtype of the eqn's first output (collectives are homogeneous)."""
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if getattr(aval, "dtype", None) is not None:
            return aval.dtype
    return None


# -- the shared donation audit ------------------------------------------

# the lowered markers jit (jax 0.9.0) emits for a donated input:
# ``tf.aliasing_output`` pins the input to an output of the SAME shape
# and dtype at lowering time; ``jax.buffer_donor`` hands the buffer to
# XLA to place during compilation — the sharded / mesh path, where
# output layout is XLA's call, AND every donor that matched no output's
# shape+dtype but shares an element count with one (mlir._set_up_aliases).
# So a marker is no longer proof of a usable donation: a donor whose
# dtype matches no output is marked ``jax.buffer_donor`` without a
# warning, and XLA — which only reuses a buffer for an output of the
# same byte size — then copies silently forever after. Only a donor with
# no output of even the same element count gets no marker (and JAX's
# one warning).
ALIAS_MARKER_ATTRS = ("tf.aliasing_output", "jax.buffer_donor")


def count_donation_markers(stablehlo: Optional[str]) -> Optional[int]:
    """Marker occurrences in lowered StableHLO text (None = not
    lowered, evidence unavailable)."""
    if stablehlo is None:
        return None
    import re as _re
    return sum(len(_re.findall(_re.escape(attr), stablehlo))
               for attr in ALIAS_MARKER_ATTRS)


def count_unplaceable_donors(ctx: "LintContext") -> int:
    """Declared donations with no output of the same BYTE size left to
    take them (each output serves one donor) — the buffers XLA cannot
    reuse whatever marker the lowering left on them."""
    def nbytes(aval) -> int:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize

    free = collections.Counter(
        nbytes(a) for a in ctx.jaxpr.out_avals if hasattr(a, "dtype"))
    unplaceable = 0
    for aval, donated in zip(ctx.in_avals, ctx.donated):
        if not donated:
            continue
        if free[nbytes(aval)] > 0:
            free[nbytes(aval)] -= 1
        else:
            unplaceable += 1
    return unplaceable


def donation_drop_findings(ctx: "LintContext",
                           pass_name: str = "donation",
                           alias_params: Optional[set] = None
                           ) -> "list[Finding]":
    """The ONE dropped-donation reporter, shared by the StableHLO
    donation pass (marker evidence only) and the compiled-HLO aliasing
    pass (marker + ``input_output_alias`` evidence). Called with
    ``alias_params`` — the compiled module's aliased parameter numbers
    — it names every dropped donation per-parameter, stating both what
    the StableHLO level declared and what the compiled module kept;
    called without, it audits marker survival in aggregate (the
    pre-compile approximation). One code path, so the two planes can
    never drift into reporting the same drop twice with different
    stories."""
    declared = [i for i, d in enumerate(ctx.donated) if d]
    if not declared:
        return []
    markers = count_donation_markers(ctx.stablehlo)
    findings: "list[Finding]" = []
    if alias_params is not None:
        dropped = [i for i in declared if i not in alias_params]
        marker_story = (
            "the jax.buffer_donor/tf.aliasing_output marker survived "
            "StableHLO lowering, so the drop happened inside XLA "
            "(layout/shape mismatch at compile time, or the output was "
            "claimed by another donor)"
            if markers is not None and markers >= len(declared) else
            "the StableHLO marker was ALREADY missing (the donation "
            "never reached the compiler — dtype/shape matched no "
            "output at lowering)"
            if markers is not None else
            "StableHLO text unavailable for marker evidence")
        for i in dropped:
            name = ctx.arg_names[i] if i < len(ctx.arg_names) else \
                f"param{i}"
            aval = ctx.in_avals[i] if i < len(ctx.in_avals) else None
            desc = (f" ({aval.dtype}{list(aval.shape)})"
                    if aval is not None else "")
            findings.append(Finding(
                pass_name, "error", ctx.name,
                f"donated input {name}{desc} has NO input_output_alias "
                f"entry in the COMPILED module (parameter {i}): "
                f"{marker_story}; XLA copies this buffer every "
                f"dispatch and the in-place-update HBM contract is "
                f"fiction for it", name))
        return findings
    unmarked = max(0, len(declared) - markers) if markers is not None \
        else 0
    dropped_n = max(unmarked, count_unplaceable_donors(ctx))
    if dropped_n:
        findings.append(Finding(
            pass_name, "error", ctx.name,
            f"{dropped_n} of {len(declared)} donated buffer(s) did "
            f"not survive lowering (no "
            f"{' / '.join(ALIAS_MARKER_ATTRS)} attribute, or a "
            f"jax.buffer_donor with no output of the same byte size "
            f"for XLA to place it in) — XLA will silently copy instead "
            f"of reusing them; the usual causes are a dtype/shape "
            f"mismatch between the donated input and every output, or "
            f"an output that was already claimed by another donor"))
    return findings


# -- pass registry ------------------------------------------------------

PASSES: "dict[str, Callable[[LintContext], list]]" = {}


def lint_pass(name: str):
    """Register a pass under ``name`` (the catalog key the CLI, the
    report, and DESIGN.md §9 all use)."""

    def register(fn):
        PASSES[name] = fn
        return fn

    return register


def run_passes(ctx: LintContext,
               only: Optional[list] = None) -> "list[Finding]":
    """Run the registered passes (or the ``only`` subset) over one
    context, findings concatenated in catalog order."""
    import akka_allreduce_tpu.analysis.passes  # noqa: F401  (registers)
    findings = []
    for name, fn in PASSES.items():
        if only is not None and name not in only:
            continue
        findings.extend(fn(ctx))
    return findings


# -- entry tracing ------------------------------------------------------

def _flat_args(tree_args: tuple, donate_argnums: tuple,
               static_argnums: tuple) -> tuple:
    """Flatten example args to (names, avals, donated) records, arg-major
    — the same order jit lowers them in. Static args carry no buffers
    and are skipped."""
    names, avals, donated = [], [], []
    for i, arg in enumerate(tree_args):
        if i in static_argnums:
            continue
        for path, leaf in jax.tree.flatten_with_path(arg)[0]:
            names.append(f"arg{i}" + "".join(str(p) for p in path))
            avals.append(jax.api_util.shaped_abstractify(leaf))
            donated.append(i in donate_argnums)
    return tuple(names), tuple(avals), tuple(donated)


def trace_entry(name: str, fn, args: tuple, policy: LintPolicy,
                donate_argnums: tuple = (), static_argnums: tuple = (),
                lower: bool = True,
                hlo_policy: Optional[Any] = None) -> LintContext:
    """Trace ``fn(*args)`` to a LintContext: jaxpr always; StableHLO
    text when ``lower`` (the donation pass needs it — aliasing is a
    lowering artifact, not a jaxpr one). ``fn`` may already be a jit
    wrapper (the production entry points are; linting THEIR wrapper
    keeps the declared donations in the artifact) — then
    ``donate_argnums``/``static_argnums`` only label the flat record.
    Accepts concrete arrays or ShapeDtypeStructs; never executes, and
    never compiles EAGERLY — when ``hlo_policy`` is given the context
    carries a thunk that compiles the optimized module on first
    ``ctx.hlo`` read (the ``lint --hlo`` plane pays for exactly the
    entries it lints)."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, donate_argnums=donate_argnums,
        static_argnums=static_argnums or None)
    # one trace covers both artifacts
    traced = jitted.trace(*args)
    closed = traced.jaxpr
    text = traced.lower().as_text() if lower else None
    names, avals, donated = _flat_args(args, tuple(donate_argnums),
                                       tuple(static_argnums))

    def _compile_hlo() -> str:
        # a fresh lower() (the traced one above may be consumed);
        # compile-only — nothing executes. CPU-safe by construction:
        # the same virtual mesh the trace used.
        import warnings as _warnings
        with _warnings.catch_warnings():
            # a deliberately-unusable donation (selfcheck fixtures)
            # would re-warn here; the finding is the signal, not the
            # warning
            _warnings.simplefilter("ignore")
            return jitted.lower(*args).compile().as_text()

    return LintContext(name=name, jaxpr=closed, policy=policy,
                       arg_names=names, in_avals=avals, donated=donated,
                       stablehlo=text, hlo_policy=hlo_policy,
                       # the thunk rides only on entries that opted
                       # into the HLO plane: a policy-less context must
                       # never trigger a surprise compile through a
                       # stray ctx.hlo read
                       _hlo_thunk=(_compile_hlo
                                   if hlo_policy is not None else None))
