"""Jit-compiled LEVEL-FUSED lockstep engine — the numpy engine in one XLA call.

:mod:`.engine` advances every scenario one event per *Python* iteration; each
iteration is a handful of numpy dispatches, so a sweep pays thousands of tiny
host ops.  This module transcribes the same Algorithm-2 event loop — case for
case, tolerance for tolerance — into ``jax.numpy`` float64, and fuses the
whole *workflow* into ONE jitted function.

Execution model (level fusion): the compiled plan topo-sorts the DAG into
**topology levels** (``CompiledWorkflow.levels``) — processes in one level
share no edges or gates, so their event loops are independent.  The engine
stacks every process of a level onto a leading process axis and runs ONE
``lax.while_loop`` per *level* over ``(Lp, B)`` state with fixed-shape
``(Lp, B, R)`` record buffers: the paper workflow traces to 3 loops instead
of 5, wide DAG levels get intra-level parallelism for free, and the loop trip
count per level is the *maximum* event count over its processes, not the sum.
Per-process specs (total progress, tolerances, requirement tables, resource
and ceiling slots) are padded to the level maxima at pack time; padded
resource slots never bind (infinite cap) and padded ceiling slots sit far
above any real ceiling.

The loop body is tuned for op count, not flops — XLA on CPU pays per-op
dispatch: value/slope/next-breakpoint ceiling queries share one gathered
piece lookup (:func:`_locate`), the resource-cap and burst-antiderivative
evaluations share the resource piece index, every record buffer write is ONE
``dynamic_update_slice`` per iteration (a stacked ``(nbuf, Lp, B, spi)``
block), and loop-invariant compositions (data-ceiling pre-composition, the
antiderivative piece-length tables) are hoisted out of the trace entirely —
static (non-edge-fed) ceilings are composed host-side at pack time.

Layout is shared with :mod:`repro.kernels.ppoly_eval`: every function batch
is a padded ``(B, P)`` triple ``(starts, c0, c1)`` using the kernels'
``PAD_START`` sentinel, so engine outputs hand straight to the Pallas query
ops without re-packing.

The numpy engine stays the reference backend: the test suite asserts the two
agree to float tolerance on makespans, finish times, progress curves, AND
bottleneck attribution (``share_seconds``) — on the paper workflow and on
randomized DAGs with wide and diamond levels.

Sharding: :meth:`JaxSweepEngine.solve` splits the scenario axis across
devices with ``jax.pmap`` when built with ``shards > 1`` — each device runs
the identical program on its ``B/D`` slice (no cross-device communication),
so sharded results are bit-identical to single-device up to reduction order
(there is none along B).  Callers pad B to a multiple of the device count
(:meth:`ScenarioPack.shard`).

Importing this module enables ``jax_enable_x64`` — the engine needs float64
to match the scalar solver's tolerances.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after the x64 switch)
from jax import lax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.core.ppoly import PPoly, TIME_TOL, VAL_RTOL  # noqa: E402
from repro.kernels.ppoly_eval.ref import PAD_START  # noqa: E402

from .engine import BatchProcResult  # noqa: E402
from .plin import BPL, UnsupportedScenario, compose_scalar  # noqa: E402

__all__ = ["IterationLadderExhausted", "JaxSweepEngine", "LazyCeilings",
           "DEFAULT_ITER_CAP", "MAX_ITER_CAP", "trace_report"]


class IterationLadderExhausted(UnsupportedScenario):
    """The adaptive iteration ladder hit ``MAX_ITER_CAP`` and gave up.

    A subclass of :class:`UnsupportedScenario`, so ``backend="auto"``
    callers transparently fall back to the numpy reference engine; the
    analysis service additionally records the decline as a degradation
    (``Report.engine_fallback`` / ``ServiceStats.degrade_reasons``).
    """


class LazyCeilings:
    """List-like ceilings materialized on first access.

    The compiled sweep does not ship its (re-derivable) ceiling arrays back
    from the device — they are only read by the occasional
    ``Report.data_ceiling`` query, and returning them taxes every re-sweep.
    ``thunk`` recomputes them host-side (numpy ``compose_scalar``) on demand.
    """

    def __init__(self, thunk):
        self._thunk = thunk
        self._val: list | None = None

    def _get(self) -> list:
        if self._val is None:
            self._val = list(self._thunk())
            self._thunk = None
        return self._val

    def __iter__(self):
        return iter(self._get())

    def __getitem__(self, i):
        return self._get()[i]

    def __len__(self):
        return len(self._get())


class _ProgressFetch:
    """The progress of every process of one solve, left on the device until
    something reads one: the first read brings all of it back in one
    ``jax.device_get`` and drops the device buffers.

    ``dev`` maps a process name to its progress arrays as the compiled run
    returned them; ``host`` un-shards and slices a fetched array as
    :meth:`JaxSweepEngine._wrap` does.  Any thread may read (the service's
    worker, or a caller's curve query).
    """

    def __init__(self, engine: "JaxSweepEngine", dev: dict, host):
        self._engine, self._dev, self._host = engine, dev, host
        self._val: dict | None = None
        self._lock = threading.Lock()

    def get(self, name: str) -> BPL:
        with self._lock:
            if self._val is None:
                with TraceAnnotation("bm.engine.fetch_progress"):
                    got = jax.device_get(self._dev)
                self._engine._count_fetch(got, progress=True)
                self._val = {n: BPL(*(self._host(a) for a in arrs))
                             for n, arrs in got.items()}
                self._engine = self._dev = self._host = None
        return self._val[name]


_INF = float("inf")

#: value of a padded (inert) ceiling slot: far above any real ceiling, far
#: below the PAD_START sentinel so it can never read as padding
_PAD_CEIL = 9e29

#: initial lockstep iteration budget of the compiled loop (events per
#: scenario are typically a handful); doubled adaptively up to MAX_ITER_CAP
#: when a solve reports overflow, at the cost of one recompile per doubling.
#: Kept small on purpose: record buffers, progress pieces, and downstream
#: ceiling compositions all scale with the budget, so an oversized cap taxes
#: EVERY sweep to spare rare ones a recompile.
DEFAULT_ITER_CAP = 8
MAX_ITER_CAP = 1024


# ---------------------------------------------------------------------------
# batched piecewise-polynomial algebra on (starts, c0, c1[, c2]) tuples — the
# jnp transcription of repro.sweep.plin.BPL (identical semantics, float64).
# The tuple ARITY is the static degree signature: 3 = piecewise-linear,
# 4 = quadratic; every helper dispatches on it at trace time, so linear
# sweeps keep the exact pre-quadratic op structure.
# ---------------------------------------------------------------------------

def _valid(s):
    return s < PAD_START * 0.5


def _piece_idx(s, t, tol):
    """Piece index per query: ``s (..., P)``, ``t (...)`` -> ``(...)``."""
    cmp = s <= (t[..., None] + tol)
    return jnp.maximum(cmp.sum(-1) - 1, 0)


def _gather(a, i):
    return jnp.take_along_axis(a, i[..., None], -1)[..., 0]


def _locate(f, t):
    """Piece index AND next breakpoint after ``t`` from ONE comparison.

    ``s > t + TIME_TOL`` is exactly the complement of the right-eval piece
    test ``s <= t + TIME_TOL``, so the two per-iteration queries the loop
    body makes against every function (value/slope at ``t``, next event
    breakpoint) share a single ``(..., P)`` comparison — on CPU each saved
    op is a saved dispatch.
    """
    s = f[0]
    cmp = s <= (t[..., None] + TIME_TOL)
    i = jnp.maximum(cmp.sum(-1) - 1, 0)
    nb = jnp.where(_valid(s) & ~cmp, s, _INF).min(-1)
    return i, nb


def _eval(f, t, tol):
    s, c0, c1 = f[:3]
    i = _piece_idx(s, t, tol)
    u = t - _gather(s, i)
    if len(f) == 4:
        return _gather(c0, i) + (_gather(c1, i) + _gather(f[3], i) * u) * u
    return _gather(c0, i) + _gather(c1, i) * u


def _eval_right(f, t):
    return _eval(f, t, TIME_TOL)


def _eval_left(f, t):
    return _eval(f, t, -TIME_TOL)


def _eval_slope_right(f, t):
    """(value, slope) at ``t`` sharing one piece-index computation."""
    s, c0, c1 = f[:3]
    i = _piece_idx(s, t, TIME_TOL)
    sl = _gather(c1, i)
    u = t - _gather(s, i)
    if len(f) == 4:
        q = _gather(f[3], i)
        return _gather(c0, i) + (sl + q * u) * u, sl + 2.0 * q * u
    return _gather(c0, i) + sl * u, sl


def _eval_slope_quad_right(f, t):
    """(value, slope, quad) at ``t`` — the quadratic widening of
    :func:`_eval_slope_right` (one shared piece lookup)."""
    s, c0, c1 = f[:3]
    i = _piece_idx(s, t, TIME_TOL)
    sl = _gather(c1, i)
    u = t - _gather(s, i)
    if len(f) == 4:
        q = _gather(f[3], i)
        return _gather(c0, i) + (sl + q * u) * u, sl + 2.0 * q * u, q
    return _gather(c0, i) + sl * u, sl, jnp.zeros_like(sl)


def _first_pos_root(a, b, c, tol=TIME_TOL):
    """Smallest root ``> tol`` of ``a·u² + b·u + c`` (inf when none) — the
    jnp twin of :func:`repro.core.ppoly.first_pos_root` (stable q-branch).

    The discriminant clamp floor is a denormal-range epsilon rather than an
    exact 0.0: ``sqrt``'s VJP is ``ct / (2·sqrt)``, so a clamp landing on
    exactly zero (every padded all-zero slot has ``disc == 0``) turns even a
    zero cotangent into ``0/0 = NaN`` and poisons the reverse-mode makespan
    gradient (:meth:`JaxSweepEngine.make_diff_run`).  The 1e-300 floor
    perturbs forward values by at most 1e-150 — far below every solver
    tolerance — and keeps the backward pass finite."""
    lin = jnp.where(b != 0.0, -c / jnp.where(b != 0.0, b, 1.0), _INF)
    disc = b * b - 4.0 * a * c
    sq = jnp.sqrt(jnp.maximum(disc, 1e-300))
    q = -0.5 * (b + jnp.where(b >= 0.0, sq, -sq))
    r1 = jnp.where(a != 0.0, q / jnp.where(a != 0.0, a, 1.0), _INF)
    r2 = jnp.where(q != 0.0, c / jnp.where(q != 0.0, q, 1.0), _INF)
    quad = jnp.minimum(jnp.where(r1 > tol, r1, _INF),
                       jnp.where(r2 > tol, r2, _INF))
    quad = jnp.where(disc >= 0.0, quad, _INF)
    return jnp.where(a == 0.0, jnp.where(lin > tol, lin, _INF), quad)


def _piece_len(f):
    """Per-piece domain length (loop-invariant — hoisted out of the body)."""
    s = f[0]
    nxt = jnp.concatenate([s[..., 1:], jnp.full(s.shape[:-1] + (1,), PAD_START)],
                          -1)
    return nxt - s


def _first_at_or_above(f, y, t_lo=None, plen=None):
    s, c0, c1 = f[:3]
    y_ = y[..., None]
    if plen is None:
        plen = _piece_len(f)
    tol = VAL_RTOL * jnp.maximum(1.0, jnp.abs(y_)) + 1e-12
    cand = jnp.where(c0 >= y_ - tol, s, _INF)
    if len(f) == 4:
        # exact quadratic crossing: pieces are monotone nondecreasing on
        # their valid domain, so the smallest positive root is the crossing
        u = _first_pos_root(jnp.broadcast_to(f[3], (y_ - c0).shape), c1,
                            c0 - y_, tol=0.0)
        ok = (c0 < y_ - tol) & (u <= plen + TIME_TOL)
    else:
        u = (y_ - c0) / jnp.where(c1 > 0, c1, 1.0)
        ok = (c1 > 0) & (c0 < y_ - tol) & (u <= plen + TIME_TOL)
    cand = jnp.minimum(cand, jnp.where(ok, s + u, _INF))
    cand = jnp.where(_valid(s), cand, _INF)
    out = cand.min(-1)
    if t_lo is not None:
        out = jnp.where(jnp.isfinite(out), jnp.maximum(out, t_lo), out)
    return out


def _antiderivative(f, linear_rate: bool = False):
    s, c0, c1 = f[:3]
    nxt = jnp.concatenate([s[..., 1:], jnp.full(s.shape[:-1] + (1,), PAD_START)],
                          -1)
    plen = jnp.where(nxt < PAD_START * 0.5, nxt - s, 0.0)
    if linear_rate:  # ramped rates: trapezoid areas, quadratic result
        areas = jnp.where(_valid(s), (c0 + 0.5 * c1 * plen) * plen, 0.0)
        acc = jnp.concatenate([jnp.zeros(s.shape[:-1] + (1,)),
                               jnp.cumsum(areas, -1)[..., :-1]], -1)
        return (s, acc, c0, 0.5 * c1)
    areas = jnp.where(_valid(s), c0 * plen, 0.0)
    acc = jnp.concatenate([jnp.zeros(s.shape[:-1] + (1,)),
                           jnp.cumsum(areas, -1)[..., :-1]], -1)
    return (s, acc, c0)


def _insert_col(cols, cvals):
    """Insert one column (start + per-plane values) into row-sorted planes —
    a shifted-select, O(B*P), in place of a row sort."""
    S = cols[0]
    P = S.shape[1]
    pos = (S <= cvals[0][:, None]).sum(1)[:, None]
    j = jnp.arange(P + 1)[None, :]

    def ins(X, xcol):
        below = jnp.concatenate([X, X[:, -1:]], 1)       # X_j   (j < pos)
        above = jnp.concatenate([X[:, :1], X], 1)        # X_{j-1} (j > pos)
        return jnp.where(j < pos, below,
                         jnp.where(j == pos, xcol[:, None], above))

    return tuple(ins(X, xc) for X, xc in zip(cols, cvals))


def _compose(outer, inner, B):
    """``outer(inner(t))`` for a static scalar pw-linear ``outer`` (np triple)
    and a batched monotone ``inner`` of degree <= 2 — plin.compose_scalar in
    jnp.  A linear outer maps each inner piece affinely, so the result keeps
    the inner's arity.

    The numpy twin concatenates breakpoint candidates, row-sorts them, and
    re-evaluates the inner function at every merged start.  Here the inner
    pieces already carry their (value, slope[, quad]) at their own starts,
    so only the outer-breakpoint crossings — one ``(B,)`` column per outer
    piece — need evaluating, and each column is merged by positional
    insertion.  No sort, no (B, M, P) evaluation blowup: XLA on CPU pays
    dearly for both.

    Only EDGE-FED ceilings (whose inner is an upstream progress computed in
    the same trace) go through this in-trace path; static ceilings are
    composed host-side at pack time (:meth:`JaxSweepEngine.level_args`).
    """
    quad = len(inner) == 4
    planes = inner
    if len(outer[0]) == 1:  # single-piece outer: a pure affine transform
        S, V, SL = inner[:3]
        s0, a0, a1 = (float(x[0]) for x in outer)
        pad = S >= PAD_START * 0.5
        out = (S, jnp.where(pad, 0.0, a0 + a1 * (V - s0)),
               jnp.where(pad, 0.0, a1 * SL))
        if quad:
            out = out + (jnp.where(pad, 0.0, a1 * inner[3]),)
        return out
    o_s, o_c0, o_c1 = (jnp.asarray(a) for a in outer)
    for v in outer[0][1:]:  # static python loop over outer breakpoints
        cross = _first_at_or_above(inner, jnp.full(B, float(v)))
        cs = jnp.where(jnp.isfinite(cross), cross, PAD_START)
        if quad:
            cv, csl, cqd = _eval_slope_quad_right(inner, cs)
            planes = _insert_col(planes, (cs, cv, csl, cqd))
        else:
            cv, csl = _eval_slope_right(inner, cs)
            planes = _insert_col(planes, (cs, cv, csl))
    S, V, SL = planes[:3]
    oi = jnp.maximum(jnp.searchsorted(o_s, V + TIME_TOL, side="right") - 1, 0)
    c0 = o_c0[oi] + o_c1[oi] * (V - o_s[oi])
    c1 = o_c1[oi] * SL
    pad = S >= PAD_START * 0.5
    out = (S, jnp.where(pad, 0.0, c0), jnp.where(pad, 0.0, c1))
    if quad:
        out = out + (jnp.where(pad, 0.0, o_c1[oi] * planes[3]),)
    return out


# ---------------------------------------------------------------------------
# static workflow structure (everything the trace closes over)
# ---------------------------------------------------------------------------

def _ppoly_triple(fn: PPoly):
    if not fn.is_piecewise_linear:
        raise UnsupportedScenario(
            f"jax engine requires piecewise-linear functions (degree {fn.degree})")
    s = fn.starts.astype(np.float64)
    c0 = fn.coeffs[:, 0].astype(np.float64)
    c1 = (fn.coeffs[:, 1].astype(np.float64) if fn.coeffs.shape[1] > 1
          else np.zeros(len(s)))
    return s, c0, c1


@dataclass(frozen=True)
class _ProcSpec:
    name: str
    p_end: float
    data_names: tuple[str, ...]
    gate_names: tuple[str, ...]
    #: dep -> (src process, output-fn triple) for pipelined (edge-fed) deps
    edges: dict
    #: dep -> requirement triple for edge-fed deps (in-trace composition)
    reqs: dict
    #: dep -> requirement PPoly for static deps (host-side pre-composition)
    req_fns: dict
    res_names: tuple[str, ...]
    #: per resource: (breakpoints, marginal slopes, jump magnitudes)
    res_tables: tuple


@dataclass(frozen=True, eq=False)
class _LevelSpec:
    """One topology level: the static, level-padded view of its processes.

    This is the engine's compile key at level granularity — everything the
    trace specializes on (process count, ceiling/resource slot maxima,
    burst presence, requirement tables) lives here, so two workflows with
    the same level signature produce the same loop structure.
    """

    procs: tuple[_ProcSpec, ...]
    nC: int                 # max ceiling slots over the level's processes
    Lr: int                 # max resource slots over the level's processes
    n_rb: int               # max requirement-table rows (padded with +inf)
    has_jumps: bool         # any burst (jump) requirement in the level
    static_ceils: bool      # True when NO process has edge-fed deps
    #: True when a LATER level composes against this level's progress —
    #: only then is the progress assembled inline; all other levels join
    #: one deferred stacked assembly at the end of the trace
    progress_inline: bool
    p_end: np.ndarray       # (Lp, 1)
    ptol: np.ndarray        # (Lp, 1) progress tolerance (per-process scale)
    ftol: np.ndarray        # (Lp, 1) finish tolerance
    jtol: np.ndarray        # (Lp, 1) jump tolerance
    rbs: np.ndarray | None      # (Lr, Lp, 1, n_rb) requirement breakpoints
    rc1s: np.ndarray | None     # (Lr, Lp, 1, n_rb) marginal slopes
    jumpss: np.ndarray | None   # (Lr, Lp, 1, n_rb) burst jump magnitudes


@dataclass(frozen=True, eq=False)
class _WorkflowSpec:
    procs: tuple[_ProcSpec, ...]        # topo order (for result unwrapping)
    levels: tuple[_LevelSpec, ...]

    @staticmethod
    def from_plan(plan) -> "_WorkflowSpec":
        wf = plan.workflow
        by_name: dict[str, _ProcSpec] = {}
        for name in plan.order:
            proc = wf.processes[name]
            edges = {dep: (src, _ppoly_triple(wf.processes[src].outputs[out]))
                     for (src, out, dep) in plan.edges_in[name]}
            reqs = {d: _ppoly_triple(dd.requirement)
                    for d, dd in proc.data.items() if d in edges}
            req_fns = {d: dd.requirement
                       for d, dd in proc.data.items() if d not in edges}
            tables = tuple((rb, rc1, jumps)
                           for (_l, rb, rc1, jumps) in plan.res_tables[name])
            by_name[name] = _ProcSpec(
                name=name, p_end=float(proc.total_progress),
                data_names=tuple(proc.data.keys()),
                gate_names=tuple(plan.gates.get(name, [])),
                edges=edges, reqs=reqs, req_fns=req_fns,
                res_names=tuple(l for (l, *_r) in plan.res_tables[name]),
                res_tables=tables)
        edge_srcs = {src for ps in by_name.values()
                     for (src, _fn) in ps.edges.values()}
        levels = []
        for names in plan.levels:
            lprocs = tuple(by_name[n] for n in names)
            Lp = len(lprocs)
            nC = max(max(len(ps.data_names), 1) for ps in lprocs)
            Lr = max(len(ps.res_names) for ps in lprocs)
            has_jumps = any(np.any(j > 0) for ps in lprocs
                            for (_rb, _c, j) in ps.res_tables)
            n_rb = max((len(rb) for ps in lprocs
                        for (rb, _c, _j) in ps.res_tables), default=1)
            if Lr:
                rbs = np.full((Lr, Lp, 1, n_rb), _INF)
                rc1s = np.zeros((Lr, Lp, 1, n_rb))
                jumpss = np.zeros((Lr, Lp, 1, n_rb))
                for pi, ps in enumerate(lprocs):
                    for li, (rb, rc1, jumps) in enumerate(ps.res_tables):
                        rbs[li, pi, 0, :len(rb)] = rb
                        rc1s[li, pi, 0, :len(rb)] = rc1
                        jumpss[li, pi, 0, :len(rb)] = jumps
            else:
                rbs = rc1s = jumpss = None
            p_end = np.array([[ps.p_end] for ps in lprocs])
            levels.append(_LevelSpec(
                procs=lprocs, nC=nC, Lr=Lr, n_rb=n_rb, has_jumps=has_jumps,
                static_ceils=all(not ps.edges for ps in lprocs),
                progress_inline=any(ps.name in edge_srcs for ps in lprocs),
                p_end=p_end,
                ptol=1e-9 * np.maximum(1.0, p_end),
                ftol=1e-9 * np.maximum(1.0, p_end),
                jtol=1e-12 * np.maximum(1.0, p_end),
                rbs=rbs, rc1s=rc1s, jumpss=jumpss))
        return _WorkflowSpec(tuple(by_name[n] for n in plan.order),
                             tuple(levels))


# ---------------------------------------------------------------------------
# one topology level: the Algorithm-2 lockstep loop as ONE lax.while_loop
# over every process of the level (leading process axis Lp)
# ---------------------------------------------------------------------------

def _solve_level(ls: _LevelSpec, C, IR, t0, B: int, iter_cap: int,
                 ramps: bool = False, fixed_iters: bool = False,
                 need_share: bool = True):
    """Mirror of ``engine.solve_batch``'s event loop, stacked over the
    ``Lp`` processes of one topology level, with fixed-size record buffers
    (two slots per iteration: burst-stall, then movement).

    State is ``(Lp, B)``; ceilings ``C`` come stacked as ``(nC, Lp, B, P)``
    and resource inputs ``IR`` as ``(Lr, Lp, B, P)``, so every
    per-iteration query is a single fused-width op across the whole level —
    XLA on CPU pays per-op dispatch, so op count is what the loop body
    optimizes.  Padded ceiling slots sit at ``_PAD_CEIL`` (never the min);
    padded resource slots have zero marginal requirement (infinite cap,
    never binding).

    ``ramps`` is the static degree switch: False keeps the piecewise-linear
    trace unchanged; True widens the existing ops to the quadratic class
    (time-varying caps, curved ceilings, quadratic motion) — every event
    stays one closed-form :func:`_first_pos_root` instead of a division, so
    the per-iteration op count grows only by the two genuinely new event
    families (governor change, tangency tie-break).

    ``fixed_iters`` swaps the ``lax.while_loop`` for a fixed-trip-count
    ``lax.scan`` of exactly ``iter_cap`` body steps, which makes the whole
    level REVERSE-MODE DIFFERENTIABLE (``while_loop`` has no transpose
    rule).  The body is already a no-op once every scenario is done — every
    state update is masked on ``act`` — so the extra trailing steps change
    nothing except wall time; the iteration counter stops advancing when
    nothing is active so the record scatter cannot clamp onto (and zero the
    mask of) the last real slot.  ``need_share=False`` additionally skips
    the bottleneck-share aggregation, which the differentiable makespan path
    (:meth:`JaxSweepEngine.make_diff_run`) never reads.
    """
    Lp = len(ls.procs)
    nC, Lr, n_rb = ls.nC, ls.Lr, ls.n_rb
    has_jumps = ls.has_jumps
    p_end = jnp.asarray(ls.p_end)                       # (Lp, 1)
    ptol = jnp.asarray(ls.ptol)
    ftol = jnp.asarray(ls.ftol)
    jtol = jnp.asarray(ls.jtol)
    spi = 2 if has_jumps else 1                         # record slots per iter
    R = spi * iter_cap
    nbuf = 6 if ramps else 5                            # T, C0, C1, A, M[, C2]
    if Lr:
        As = _antiderivative(IR, linear_rate=ramps) if has_jumps else None
        A_plen = _piece_len(As) if has_jumps else None  # hoisted, invariant
        rbs = jnp.asarray(ls.rbs)                       # (Lr, Lp, 1, n_rb)
        rc1s = jnp.broadcast_to(jnp.asarray(ls.rc1s), (Lr, Lp, B, n_rb))
        jumpss = jnp.broadcast_to(jnp.asarray(ls.jumpss), (Lr, Lp, B, n_rb))

    def cond(st):
        return (st["it"] < iter_cap) & jnp.any(st["active"]
                                               & (st["p"] < p_end - ftol))

    def body(st):
        t, p = st["t"], st["p"]                         # (Lp, B)
        finish, active = st["finish"], st["active"]
        absorbed = st["absorbed"]                       # (Lr, Lp, B, n_rb)
        it = st["it"]
        act = active & (p < p_end - ftol)
        any_act = jnp.any(act)

        # ---- ceilings at t: value/slope/next-break from ONE piece lookup ---
        tC = jnp.broadcast_to(t, (nC, Lp, B))
        iC, nbC = _locate(C, tC)
        uC = tC - _gather(C[0], iC)
        slC = _gather(C[2], iC)
        if ramps:
            Q = _gather(C[3], iC)
            V = _gather(C[1], iC) + (slC + Q * uC) * uC             # (nC,Lp,B)
            S = slC + 2.0 * Q * uC
        else:
            V = _gather(C[1], iC) + slC * uC                        # (nC,Lp,B)
            S = slC
        if nC > 1:
            # value ties break on slope, then curvature: the ceiling that is
            # lower just after t governs (mirrors the numpy twin)
            vmin = V.min(0)
            tie = V <= vmin + VAL_RTOL * jnp.maximum(1.0, jnp.abs(vmin))
            St = jnp.where(tie, S, _INF)
            if ramps:
                Smin = St.min(0)
                tie = tie & (St <= Smin + VAL_RTOL * jnp.maximum(
                    1.0, jnp.abs(Smin)))
                St = jnp.where(tie, Q, _INF)
            kstar = jnp.argmin(St, 0).astype(jnp.int32)
            pd = jnp.take_along_axis(V, kstar[None], 0)[0]
            pdslope = jnp.take_along_axis(S, kstar[None], 0)[0]
            if ramps:
                pdq = jnp.take_along_axis(Q, kstar[None], 0)[0]
        else:
            kstar = jnp.zeros((Lp, B), jnp.int32)
            pd, pdslope = V[0], S[0]
            if ramps:
                pdq = Q[0]
        tb_ceil = nbC.min(0)

        # ---- resource caps and next requirement breakpoints ----------------
        # the cap query and (when bursts exist) the antiderivative value
        # share the resource piece index: antiderivatives keep their rate's
        # piece starts, so one _locate serves r_now, tb_ir AND A(t)
        if Lr:
            tL = jnp.broadcast_to(t, (Lr, Lp, B))
            iL, nbL = _locate(IR, tL)
            uL = tL - _gather(IR[0], iL)
            r_sl = _gather(IR[2], iL)
            r_now = _gather(IR[1], iL) + r_sl * uL
            tb_ir = nbL.min(0)
            ri = jnp.maximum((rbs <= (p + ptol)[None, :, :, None]).sum(-1) - 1,
                             0)                                     # (Lr,Lp,B)
            cl = _gather(rc1s, ri)
            caps = jnp.where(cl > 0, r_now / jnp.where(cl > 0, cl, 1.0), _INF)
            if ramps:
                caps1 = jnp.where(cl > 0, r_sl / jnp.where(cl > 0, cl, 1.0),
                                  0.0)
            pp = p[None, :, :, None]
            if has_jumps:
                cond_bp = ((rbs >= pp - ptol[None, :, :, None]) & ~absorbed
                           & ((jumpss > 0) | (rbs > pp + ptol[None, :, :, None])))
            else:  # no jumps: nothing is ever absorbed, zero-jump rule only
                cond_bp = (rbs >= pp - ptol[None, :, :, None]) \
                    & (rbs > pp + ptol[None, :, :, None])
            has = cond_bp.any(-1)
            pbidx = jnp.argmax(cond_bp, -1)                         # (Lr,Lp,B)
            pb = jnp.where(has,
                           _gather(jnp.broadcast_to(rbs, (Lr, Lp, B, n_rb)),
                                   pbidx),
                           _INF)
            if Lr > 1 and ramps:
                smin = caps.min(0)
                # value ties break on the cap derivative (falling cap wins)
                smin_s = jnp.where(jnp.isfinite(smin), smin, 1.0)
                ctie = caps <= smin + VAL_RTOL * jnp.maximum(1.0, jnp.abs(smin_s))
                lstar = jnp.argmin(jnp.where(ctie, caps1, _INF), 0).astype(jnp.int32)
                smin1 = jnp.where(jnp.isfinite(smin),
                                  jnp.take_along_axis(caps1, lstar[None], 0)[0],
                                  0.0)
            elif Lr > 1:
                smin = caps.min(0)
                lstar = caps.argmin(0)
            else:
                smin = caps[0]
                lstar = jnp.zeros((Lp, B), jnp.int32)
                if ramps:
                    smin1 = jnp.where(jnp.isfinite(smin), caps1[0], 0.0)
            if has_jumps:
                pjump = jnp.where(
                    has, _gather(jumpss, pbidx), 0.0)
        else:
            tb_ir = jnp.full((Lp, B), _INF)
            smin = jnp.full((Lp, B), _INF)
            smin1 = jnp.zeros((Lp, B))
            lstar = jnp.zeros((Lp, B), kstar.dtype)
            pb = jnp.zeros((0, Lp, B))

        # ---- unconstrained: jump instantly toward the data ceiling ---------
        uncon = act & ~jnp.isfinite(smin) & (p < pd - jtol)
        if has_jumps:
            blk = jnp.where((pjump > 0) & (pb > p[None] + jtol[None])
                            & (pb <= pd[None] + jtol[None]), pb, _INF)
            blk_pb = blk.min(0)
            target = jnp.where(jnp.isfinite(blk_pb), blk_pb, pd)
            p = jnp.where(uncon, target, p)
            fin_jump = uncon & ~jnp.isfinite(blk_pb) & (p >= p_end - ftol)
        else:
            p = jnp.where(uncon, pd, p)
            fin_jump = uncon & (p >= p_end - ftol)
        finish = jnp.where(fin_jump, t, finish)
        active = active & ~fin_jump
        act = act & ~fin_jump

        # ---- burst-resource stall: absorb jumps pinned at p ----------------
        if has_jumps:
            pinned = act[None] & (pjump > 0) & (jnp.abs(pb - p[None])
                                                <= ptol[None])
            uA = tL - _gather(As[0], iL)        # same pieces as the rate
            a_now = _gather(As[1], iL) + _gather(As[2], iL) * uA
            if ramps:
                a_now = a_now + _gather(As[3], iL) * uA * uA
            need = a_now + pjump
            te = _first_at_or_above(As, need, tL, plen=A_plen)
            te = jnp.where(pinned, te, -_INF)
            stall_end = te.max(0)
            # ties keep the first resource (argmax returns the first max)
            stall_attr = (nC + jnp.argmax(te, 0)).astype(jnp.int32)
            absorbed = absorbed | (pinned[..., None]
                                   & (jnp.arange(n_rb)[None, None, None]
                                      == pbidx[..., None]))
            stalled = act & (stall_end > -_INF)
            rec0 = (jnp.where(stalled, t, 0.0), jnp.where(stalled, p, 0.0),
                    jnp.zeros((Lp, B)),
                    jnp.where(stalled, stall_attr, -1).astype(jnp.float64),
                    stalled.astype(jnp.float64))
            dead = stalled & ~jnp.isfinite(stall_end)
            active = active & ~dead
            t = jnp.where(stalled & jnp.isfinite(stall_end), stall_end, t)
            act = act & ~stalled
        else:
            rec0 = None

        # ---- movement: data-limited ceiling following or min-slope ---------
        on_ceiling = p >= pd - ftol
        cap_ok = ~jnp.isfinite(smin) | (
            pdslope <= smin + 1e-12 * jnp.maximum(
                1.0, jnp.where(jnp.isfinite(smin), smin, 1.0)))
        if ramps:
            # tangency tie-break (mirrors the numpy twin): at
            # cap == ceiling-slope the rate that is lower just after t
            # governs — a falling cap binds immediately
            smin_s = jnp.where(jnp.isfinite(smin), smin, 1.0)
            eq = jnp.abs(pdslope - smin_s) <= 1e-9 * jnp.maximum(
                1.0, jnp.abs(smin_s))
            falling = smin1 < 2.0 * pdq - 1e-12 * jnp.maximum(1.0,
                                                              jnp.abs(pdq))
            cap_ok = cap_ok & ~(jnp.isfinite(smin) & eq & falling)
        data_lim = on_ceiling & cap_ok
        slope = jnp.where(data_lim, pdslope,
                          jnp.where(jnp.isfinite(smin), smin, 0.0))
        if ramps:
            qmov = jnp.where(data_lim, pdq,
                             jnp.where(jnp.isfinite(smin), 0.5 * smin1, 0.0))
        attr = jnp.where(data_lim, kstar, nC + lstar).astype(jnp.int32)

        events = jnp.stack([tb_ceil, tb_ir])
        if nC > 1:  # ceiling argmin crossover (impossible with one ceiling)
            if ramps:
                ux = _first_pos_root(Q - pdq[None], S - pdslope[None],
                                     V - pd[None])
            else:
                dv = V - pd[None]
                ds = pdslope[None] - S
                ux = jnp.where(ds > 1e-300, dv / jnp.where(ds > 1e-300, ds, 1.0),
                               _INF)
                ux = jnp.where(ux > TIME_TOL, ux, _INF)
            events = jnp.concatenate([events, t[None] + ux])
        if Lr:
            if ramps:
                upb = _first_pos_root(qmov[None], slope[None],
                                      jnp.where(jnp.isfinite(pb),
                                                p[None] - pb, 1.0))
                upb = jnp.where(jnp.isfinite(pb), upb, _INF)
            else:
                # pb is masked to 0 BEFORE the divide: an inf numerator in an
                # unselected lane would still poison reverse-mode (the divide
                # VJP multiplies the primal quotient by a zero cotangent —
                # 0 * inf = nan) through the theta-dependent slope
                pbs = jnp.where(jnp.isfinite(pb), pb, 0.0)
                upb = jnp.where((slope[None] > 0) & jnp.isfinite(pb),
                                (pbs - p[None]) / jnp.where(slope[None] > 0,
                                                            slope[None], 1.0),
                                _INF)
                upb = jnp.where(upb > TIME_TOL, upb, _INF)
            events = jnp.concatenate([events, t[None] + upb])
        if ramps:
            # catch-up from EQUALITY is possible in the quadratic class (a
            # decelerating ceiling re-meets slower progress), so only
            # data-limited rows are exempt; the gap clamps to <= 0 so float
            # noise above the ceiling cannot schedule a bogus crossing
            ucatch = _first_pos_root(qmov - pdq, slope - pdslope,
                                     jnp.minimum(p - pd, 0.0))
            ucatch = jnp.where(~data_lim, ucatch, _INF)
        else:
            ucatch = jnp.where(~data_lim & (p < pd - jtol) & (slope > pdslope + 1e-300),
                               (pd - p) / jnp.where(slope > pdslope,
                                                    slope - pdslope, 1.0),
                               _INF)
            ucatch = jnp.where(ucatch > TIME_TOL, ucatch, _INF)
        events = jnp.concatenate([events, (t + ucatch)[None]])
        if ramps and Lr:
            # governor change: a time-varying cap undercuts the current rate
            # bound — the ceiling slope when data-limited, the minimum cap
            # when resource-limited (cap crossover); linear-in-time crossing
            base0 = jnp.where(data_lim, pdslope, smin)
            base1 = jnp.where(data_lim, 2.0 * pdq, smin1)
            db = caps1 - base1[None]
            dc = jnp.where(jnp.isfinite(caps), caps - base0[None], 1.0)
            ug = jnp.where(db != 0.0, -dc / jnp.where(db != 0.0, db, 1.0),
                           _INF)
            ug = jnp.where((ug > TIME_TOL) & jnp.isfinite(caps)
                           & jnp.isfinite(base0)[None], ug, _INF)
            events = jnp.concatenate([events, t[None] + ug])
        t_next = events.min(0)

        if ramps:
            ufin = _first_pos_root(qmov, slope, p - p_end, tol=0.0)
            t_fin = t + ufin
        else:
            ufin = jnp.where(slope > 0, (p_end - p) / jnp.where(slope > 0, slope, 1.0),
                             _INF)
            t_fin = jnp.where(ufin > 0, t + ufin, t)

        # movement record captures the pre-advance state
        rec1 = (jnp.where(act, t, 0.0), jnp.where(act, p, 0.0),
                jnp.where(act, slope, 0.0),
                jnp.where(act, attr, -1).astype(jnp.float64),
                act.astype(jnp.float64))
        if ramps:
            rec0 = rec0 + (jnp.zeros((Lp, B)),) if rec0 is not None else None
            rec1 = rec1 + (jnp.where(act, qmov, 0.0),)

        done = act & jnp.isfinite(t_fin) & (t_fin <= t_next + TIME_TOL)
        finish = jnp.where(done, t_fin, finish)
        active = active & ~done
        cont = act & ~done
        stuck = cont & ~jnp.isfinite(t_next)
        active = active & ~stuck
        adv = cont & ~stuck
        t_safe = jnp.where(jnp.isfinite(t_next), t_next, t)
        pd_left = _eval_left(C, jnp.broadcast_to(t_safe, (nC, Lp, B))).min(0)
        du = t_safe - t
        if ramps:
            p_new = jnp.minimum(p + (slope + qmov * du) * du, pd_left)
        else:
            p_new = jnp.minimum(p + slope * du, pd_left)
        p = jnp.where(adv, jnp.maximum(p, p_new), p)
        t = jnp.where(adv, t_safe, t)

        # ONE record scatter per iteration: all buffers (and, with bursts,
        # both slots) land as a single (nbuf, Lp, B, spi) block write
        rec1v = jnp.stack(rec1)                             # (nbuf, Lp, B)
        if has_jumps:
            block = jnp.stack([jnp.stack(rec0), rec1v], -1)
        else:
            block = rec1v[..., None]
        z = jnp.zeros((), it.dtype)
        rec = lax.dynamic_update_slice(st["rec"], block, (z, z, z, spi * it))

        if fixed_iters:
            # scan runs the body past quiescence; freeze the slot counter
            # there so the (all-masked) block writes land on the next FREE
            # slot instead of clamping onto — and zeroing the mask of — the
            # last real record.  `any_act` mirrors the while_loop cond.
            it_next = it + any_act.astype(it.dtype)
        else:
            it_next = it + 1
        return {"it": it_next, "t": t, "p": p, "finish": finish,
                "active": active, "absorbed": absorbed, "rec": rec}

    init = {
        "it": jnp.zeros((), jnp.int32),
        "t": t0.astype(jnp.float64),
        "p": jnp.zeros((Lp, B)),
        "finish": jnp.full((Lp, B), _INF),
        "active": jnp.ones((Lp, B), bool),
        "absorbed": (jnp.zeros((max(Lr, 1), Lp, B, n_rb), bool) if has_jumps
                     else jnp.zeros((1, 1, 1, 1), bool)),
        "rec": jnp.zeros((nbuf, Lp, B, R)),
    }
    if fixed_iters:
        st, _ = lax.scan(lambda s, _x: (body(s), None), init, None,
                         length=iter_cap)
    else:
        st = lax.while_loop(cond, body, init)

    p, t, finish, active = st["p"], st["t"], st["finish"], st["active"]
    late = active & (p >= p_end - ftol) & ~jnp.isfinite(finish)
    finish = jnp.where(late, t, finish)
    overflow = jnp.any(active & (p < p_end - ftol))
    rec = st["rec"]
    share = (_aggregate_shares(rec[0], rec[3].astype(jnp.int32), rec[4] > 0.5,
                               finish, nC + Lr, R)
             if need_share else None)
    # progress assembly happens in the runner: levels whose progress feeds
    # no later level join ONE deferred stacked assembly pass at the end
    return {"finish": finish, "rec": rec, "share": share,
            "iterations": st["it"], "overflow": overflow}


def _suffix_min(a):
    """Suffix cumulative minimum along the last axis via log-step shifted
    minima.  ``lax.cummin`` lowers to ``reduce-window`` on XLA CPU — an
    O(R²) window scan costing ~100us per call at these shapes — while this
    unrolls to ceil(log2 R) elementwise ``minimum`` ops that fuse."""
    R = a.shape[-1]
    big = jnp.asarray(np.iinfo(np.int64).max if jnp.issubdtype(a.dtype, jnp.integer)
                      else _INF, a.dtype)
    k = 1
    while k < R:
        shifted = jnp.concatenate(
            [a[..., k:], jnp.full(a.shape[:-1] + (k,), big, a.dtype)], -1)
        a = jnp.minimum(a, shifted)
        k *= 2
    return a


def _suffix_or(m):
    """Suffix cumulative OR along the last axis (log-step, fusible)."""
    R = m.shape[-1]
    k = 1
    while k < R:
        shifted = jnp.concatenate(
            [m[..., k:], jnp.zeros(m.shape[:-1] + (k,), m.dtype)], -1)
        m = m | shifted
        k *= 2
    return m


def _assemble_progress(T, C0, C1, M, t0, finish, p_end, R: int, C2=None):
    """engine._assemble_progress with a static piece budget ``P = R + 1``,
    generalized over leading batch dims (here ``(Lp, B)``).

    Instead of compacting valid pieces to the front (a stable sort — slow in
    XLA on CPU), every invalid slot is backward-filled with the NEXT valid
    piece, producing a sorted-with-duplicates layout: piece-index queries
    count ``starts <= t`` and therefore land on the LAST duplicate, which is
    the real piece, so every BPL/kernel query reads identical values.  This
    also subsumes the numpy twin's zero-width dedupe: a superseded piece
    becomes a duplicate of its successor.  The terminal hold-at-``p_end``
    piece is appended as column R; rows that never record and never finish
    anchor the domain at ``t0``.
    """
    lead = finish.shape
    ax = len(lead)
    M = M & (T < finish[..., None] - TIME_TOL)
    has_fin = jnp.isfinite(finish)
    pe = jnp.broadcast_to(p_end, lead)
    S = jnp.concatenate([T, jnp.where(has_fin, finish, PAD_START)[..., None]],
                        -1)
    C0x = jnp.concatenate([C0, jnp.where(has_fin, pe, 0.0)[..., None]], -1)
    C1x = jnp.concatenate([C1, jnp.zeros(lead + (1,))], -1)
    Mx = jnp.concatenate([M, has_fin[..., None]], -1)
    # "fill each slot from the nearest valid slot at/after it" as a suffix
    # cumulative-min over masked column indices (no sequential scan)
    P1 = R + 1
    idx = jnp.where(Mx, jnp.arange(P1), P1)
    nxt = _suffix_min(idx)
    grab = lambda a, fill: jnp.take_along_axis(  # noqa: E731
        jnp.concatenate([a, jnp.full(lead + (1,), fill)], -1), nxt, -1)
    Sf = grab(S, PAD_START)
    C0f = grab(C0x, 0.0)
    C1f = grab(C1x, 0.0)
    empty = ~Mx.any(-1)
    Sf = Sf.at[..., 0].set(jnp.where(empty, t0, Sf[..., 0]))
    if C2 is not None:
        C2f = grab(jnp.concatenate([C2, jnp.zeros(lead + (1,))], -1), 0.0)
        return (Sf, C0f, C1f, C2f)
    return (Sf, C0f, C1f)


def _aggregate_shares(T, ATTR, M, finish, n_factors: int, R: int):
    """engine._aggregate_shares with the backward column loops replaced by
    suffix cumulative reductions (record starts are non-decreasing),
    generalized over leading batch dims."""
    lead = finish.shape
    ax = len(lead)
    if n_factors == 0:
        return jnp.zeros(lead + (0,))
    # piece ends: the next valid piece's start (INF when none — clipped by
    # the effective finish below)
    idx = jnp.where(M, jnp.arange(R), R)
    nxt = _suffix_min(jnp.concatenate([idx[..., 1:],
                                       jnp.full(lead + (1,), R)], -1))
    ends_src = jnp.concatenate([jnp.where(M, T, _INF),
                                jnp.full(lead + (1,), _INF)], -1)
    ends = jnp.where(M, jnp.take_along_axis(ends_src, nxt, -1), 0.0)
    # effective finish for never-finishing rows: the START of the trailing
    # equal-attribution run of valid pieces (see the numpy twin)
    seen = M.any(-1)
    last_idx = jnp.where(M, jnp.arange(R), -1).max(-1)
    last_attr = _gather(ATTR, jnp.maximum(last_idx, 0))
    bad = M & (ATTR != last_attr[..., None])
    in_run = M & ~_suffix_or(bad)
    run_start = jnp.where(in_run, T, _INF).min(-1)
    fin_shares = jnp.where(jnp.isfinite(finish), finish,
                           jnp.where(seen & jnp.isfinite(run_start),
                                     run_start, 0.0))
    span = jnp.clip(jnp.minimum(ends, fin_shares[..., None]) - T, 0.0, None)
    span = jnp.where(M, span, 0.0)
    onehot = ATTR[..., None] == jnp.arange(n_factors, dtype=jnp.int32)
    return (span[..., None] * onehot).sum(ax)


# ---------------------------------------------------------------------------
# whole-workflow runner + engine front end
# ---------------------------------------------------------------------------

def _bcast(fn, B: int):
    if fn[0].shape[0] == B:
        return fn
    P = fn[0].shape[1]
    return tuple(jnp.broadcast_to(a, (B, P)) for a in fn)


def _stack_level_ceils(per, nC: int, B: int, arity: int):
    """Stack per-process ceiling-tuple lists into one ``(nC, Lp, B, Pmax)``
    tuple, padding missing slots with the inert far-above ceiling."""
    Pm = max(tr[0].shape[-1] for cl in per for tr in cl)
    pad_slot = None

    def padded(tr):
        tr = tuple(tr)
        if len(tr) < arity:
            tr = tr + tuple(jnp.zeros(tr[0].shape)
                            for _ in range(arity - len(tr)))
        out = []
        for k, a in enumerate(tr):
            a = jnp.broadcast_to(a, (B, a.shape[-1]))
            extra = Pm - a.shape[-1]
            if extra:
                fill = PAD_START if k == 0 else 0.0
                a = jnp.concatenate([a, jnp.full((B, extra), fill)], -1)
            out.append(a)
        return out

    rows = []
    for cl in per:
        cl = [padded(tr) for tr in cl]
        while len(cl) < nC:
            if pad_slot is None:
                s = jnp.concatenate(
                    [jnp.zeros((B, 1)), jnp.full((B, Pm - 1), PAD_START)], -1)
                c0 = jnp.concatenate(
                    [jnp.full((B, 1), _PAD_CEIL), jnp.zeros((B, Pm - 1))], -1)
                z = jnp.zeros((B, Pm))
                pad_slot = [s, c0, z] + [z] * (arity - 3)
            cl.append(pad_slot)
        rows.append(cl)
    Lp = len(per)
    return tuple(
        jnp.stack([jnp.stack([rows[pi][ci][k] for pi in range(Lp)])
                   for ci in range(nC)])
        for k in range(arity))


_ZERO_FN = (np.zeros((1, 1)), np.zeros((1, 1)), np.zeros((1, 1)))


def _np_pad_stack(slots, arity: int):
    """Host-side twin of the in-trace stacking: ``slots[n][pi]`` numpy
    tuples -> ``(n, Lp, rows, Pmax)`` arrays with ``rows in (1, B)``
    (1 only when every constituent is a single-row broadcast)."""
    Pm = max(tr[0].shape[-1] for row in slots for tr in row)
    rows_B = max(tr[0].shape[0] for row in slots for tr in row)
    out = []
    for k in range(arity):
        mats = []
        for row in slots:
            per = []
            for tr in row:
                a = (np.asarray(tr[k], np.float64) if k < len(tr)
                     else np.zeros_like(np.asarray(tr[0], np.float64)))
                if a.shape[0] != rows_B:
                    a = np.broadcast_to(a, (rows_B, a.shape[-1]))
                extra = Pm - a.shape[-1]
                if extra:
                    fill = PAD_START if k == 0 else 0.0
                    a = np.concatenate(
                        [a, np.full((a.shape[0], extra), fill)], -1)
                per.append(a)
            mats.append(np.stack(per))
        out.append(np.stack(mats))
    return tuple(out)


class JaxSweepEngine:
    """Compiled level-fused lockstep solver for one :class:`CompiledWorkflow`.

    One instance per plan; jitted executables are cached per
    ``(B, shards, iter_cap, ramps)`` — the workflow-side compile key is the
    level signature baked into :class:`_WorkflowSpec`.  ``solve`` takes the
    per-process input arrays a :class:`~repro.analysis.pack.ScenarioPack`
    prepared (``pack.host_args``) — numpy ``(rows, P)`` triples with
    ``rows in (1, B)`` — stacks them by topology level host-side
    (:meth:`level_args`), and returns the same
    :class:`~repro.sweep.engine.BatchProcResult` mapping the numpy engine
    produces.
    """

    def __init__(self, plan, *, iter_cap: int = DEFAULT_ITER_CAP):
        self.spec = _WorkflowSpec.from_plan(plan)
        self.iter_cap = int(iter_cap)
        self._compiled: dict = {}
        #: per-(B, shards) iteration budgets proven by past solves, so
        #: re-sweeps skip the overflow ladder without one deep workload
        #: ratcheting the budget (and the record-buffer tax) for all shapes
        self._proven_caps: dict = {}
        #: XLA traces actually paid by this process: the counter increments
        #: INSIDE the traced body of ``run`` (Python runs only on a jit or
        #: export cache miss), so it is ground truth for the "warm start =
        #: zero new traces" pin
        self.trace_count = 0
        #: solves served by an AOT executable adopted from a plan artifact
        self.aot_hits = 0
        #: lockstep steps run: per solve, the sum over topology levels of
        #: the level's loop trip count
        self.level_steps = 0
        #: bytes of every host array :meth:`_wrap` and the lazy progress
        #: fetch brought back from the device
        self.fetch_bytes = 0
        #: solves whose progress something read (one fetch per solve)
        self.progress_fetches = 0
        self._fetch_lock = threading.Lock()
        #: call-signature census per (B, shards, iter_cap, ramps): the input
        #: aval pytrees actually solved, recorded so :meth:`export_entries`
        #: AOT-serializes exactly the executables a warm start will need
        self._call_shapes: dict = {}
        #: adopted AOT executables: (B, shards, iter_cap, ramps) -> {sig: call}
        self._aot: dict = {}
        #: the raw serialized blobs the adopted executables came from, kept
        #: so a re-export of this engine does not drop them
        self._aot_blobs: list = []

    # -- trace construction -------------------------------------------------
    def _make_run(self, B: int, iter_cap: int, ramps: bool):
        spec = self.spec
        arity = 4 if ramps else 3

        def run(largs):
            self.trace_count += 1
            finish_by, progress_by, out = {}, {}, {}
            solved = []                 # (level, t0, result) in level order
            overflow = jnp.zeros((), bool)
            for ls, la in zip(spec.levels, largs):
                Lp = len(ls.procs)
                rows = []
                for ps in ls.procs:
                    t0p = jnp.zeros(B)
                    for g in ps.gate_names:
                        t0p = jnp.maximum(t0p, finish_by[g])
                    rows.append(t0p)
                t0 = jnp.stack(rows) if Lp > 1 else rows[0][None]
                if la["C"] is not None:   # fully static level, pre-stacked
                    C = tuple(jnp.broadcast_to(jnp.asarray(a),
                                               (ls.nC, Lp, B, a.shape[-1]))
                              for a in la["C"])
                else:
                    per = []
                    for pi, ps in enumerate(ls.procs):
                        cl = []
                        for dep in ps.data_names:
                            if dep in ps.edges:
                                src, out_fn = ps.edges[dep]
                                inner = _compose(out_fn, progress_by[src], B)
                                cl.append(_compose(ps.reqs[dep], inner, B))
                            else:
                                cl.append(_bcast(la["ceil"][f"{pi}.{dep}"], B))
                        if not cl:
                            cl = [(jnp.zeros((B, 1)),
                                   jnp.full((B, 1), ps.p_end),
                                   jnp.zeros((B, 1)))]
                        per.append(cl)
                    C = _stack_level_ceils(per, ls.nC, B, arity)
                IR = (tuple(jnp.broadcast_to(jnp.asarray(a),
                                             (ls.Lr, Lp, B, a.shape[-1]))
                            for a in la["IR"])
                      if ls.Lr else None)
                res = _solve_level(ls, C, IR, t0, B, iter_cap, ramps)
                overflow = overflow | res["overflow"]
                solved.append((ls, t0, res))
                for pi, ps in enumerate(ls.procs):
                    finish_by[ps.name] = res["finish"][pi]
                if ls.progress_inline:  # a later level composes against it
                    rec = res["rec"]
                    prog = _assemble_progress(
                        rec[0], rec[1], rec[2], rec[4] > 0.5, t0,
                        res["finish"], jnp.asarray(ls.p_end),
                        rec.shape[-1], C2=rec[5] if ramps else None)
                    for pi, ps in enumerate(ls.procs):
                        progress_by[ps.name] = tuple(a[pi] for a in prog)

            # ---- deferred progress: ONE stacked assembly over the levels no
            # later level composes against (dispatch cost is per op, so the
            # terminal levels share a single padded pass)
            deferred = [(ls, t0, res) for (ls, t0, res) in solved
                        if not ls.progress_inline]
            if deferred:
                Rd = max(res["rec"].shape[-1] for (_ls, _t0, res) in deferred)

                def padR(a, target):
                    extra = target - a.shape[-1]
                    if not extra:
                        return a
                    return jnp.concatenate(
                        [a, jnp.full(a.shape[:-1] + (extra,), 0.0, a.dtype)],
                        -1)

                dcat = lambda k: jnp.concatenate(  # noqa: E731
                    [padR(res["rec"][k], Rd)
                     for (_ls, _t0, res) in deferred], 0)
                prog = _assemble_progress(
                    dcat(0), dcat(1), dcat(2), dcat(4) > 0.5,
                    jnp.concatenate([t0 for (_ls, t0, _r) in deferred], 0),
                    jnp.concatenate([res["finish"]
                                     for (_ls, _t0, res) in deferred], 0),
                    jnp.asarray(np.concatenate(
                        [ls.p_end for (ls, _t0, _r) in deferred], 0)),
                    Rd, C2=dcat(5) if ramps else None)
                row = 0
                for ls, _t0, _res in deferred:
                    for pi, ps in enumerate(ls.procs):
                        progress_by[ps.name] = tuple(a[row + pi]
                                                     for a in prog)
                    row += len(ls.procs)

            for ls, _t0, res in solved:
                for pi, ps in enumerate(ls.procs):
                    K, L = len(ps.data_names), len(ps.res_names)
                    cols = np.array(list(range(K))
                                    + list(range(ls.nC, ls.nC + L)), np.int32)
                    out[ps.name] = {
                        "finish": res["finish"][pi],
                        "progress": progress_by[ps.name],
                        "share": res["share"][pi][:, cols],
                        "iterations": res["iterations"],
                    }
            out["__overflow__"] = overflow
            return out

        return run

    # -- differentiable makespan path ---------------------------------------
    def make_diff_run(self, B: int, iter_cap: int, ramps: bool,
                      apply_theta=None):
        """A REVERSE-MODE DIFFERENTIABLE ``makespan(theta)`` through the
        level-fused event loop — the engine half of ``plan.optimize()``.

        Returns ``run(largs, theta) -> (makespans (B,), overflow ())`` built
        from the same level recursion as :meth:`_make_run`, with two
        changes that make ``jax.grad`` work end to end:

        * every level loop runs as a fixed-trip-count ``lax.scan``
          (``fixed_iters=True`` in :func:`_solve_level`) — ``while_loop``
          has no transpose rule — and skips the share aggregation the
          makespan never reads;
        * ``apply_theta(IR, level_index, theta)`` rescales / rebuilds
          resource-input planes IN-TRACE from the flat ``theta`` batch
          (see :class:`repro.analysis.pack.ThetaMap`), so every candidate
          evaluation and its gradient ride one fused ``(B,)`` sweep with no
          host re-packing.

        Differentiability is the implicit-function-theorem kind: at generic
        ``theta`` the event order and binding constraints are locally
        constant, every event time is a closed form (division or
        :func:`_first_pos_root`), and gradients flow through the selected
        branches of the piecewise minima — exactly the quantity central
        finite differences measure away from event-reorder points.  The
        returned ``overflow`` flag is the caller's signal to climb the
        iteration ladder (retrace with a doubled ``iter_cap``), with the
        same :data:`MAX_ITER_CAP` ceiling as the regular solve.
        """
        spec = self.spec
        arity = 4 if ramps else 3

        def run(largs, theta):
            finish_by, progress_by = {}, {}
            overflow = jnp.zeros((), bool)
            makespan = jnp.zeros((B,))
            for li, (ls, la) in enumerate(zip(spec.levels, largs)):
                Lp = len(ls.procs)
                rows = []
                for ps in ls.procs:
                    t0p = jnp.zeros(B)
                    for g in ps.gate_names:
                        t0p = jnp.maximum(t0p, finish_by[g])
                    rows.append(t0p)
                t0 = jnp.stack(rows) if Lp > 1 else rows[0][None]
                if la["C"] is not None:   # fully static level, pre-stacked
                    C = tuple(jnp.broadcast_to(jnp.asarray(a),
                                               (ls.nC, Lp, B, a.shape[-1]))
                              for a in la["C"])
                else:
                    per = []
                    for pi, ps in enumerate(ls.procs):
                        cl = []
                        for dep in ps.data_names:
                            if dep in ps.edges:
                                src, out_fn = ps.edges[dep]
                                inner = _compose(out_fn, progress_by[src], B)
                                cl.append(_compose(ps.reqs[dep], inner, B))
                            else:
                                cl.append(_bcast(la["ceil"][f"{pi}.{dep}"], B))
                        if not cl:
                            cl = [(jnp.zeros((B, 1)),
                                   jnp.full((B, 1), ps.p_end),
                                   jnp.zeros((B, 1)))]
                        per.append(cl)
                    C = _stack_level_ceils(per, ls.nC, B, arity)
                IR = (tuple(jnp.broadcast_to(jnp.asarray(a),
                                             (ls.Lr, Lp, B, a.shape[-1]))
                            for a in la["IR"])
                      if ls.Lr else None)
                if IR is not None and apply_theta is not None:
                    IR = apply_theta(IR, li, theta)
                res = _solve_level(ls, C, IR, t0, B, iter_cap, ramps,
                                   fixed_iters=True, need_share=False)
                overflow = overflow | res["overflow"]
                for pi, ps in enumerate(ls.procs):
                    finish_by[ps.name] = res["finish"][pi]
                makespan = jnp.maximum(makespan, res["finish"].max(0))
                if ls.progress_inline:  # a later level composes against it
                    rec = res["rec"]
                    prog = _assemble_progress(
                        rec[0], rec[1], rec[2], rec[4] > 0.5, t0,
                        res["finish"], jnp.asarray(ls.p_end),
                        rec.shape[-1], C2=rec[5] if ramps else None)
                    for pi, ps in enumerate(ls.procs):
                        progress_by[ps.name] = tuple(a[pi] for a in prog)
            return makespan, overflow

        return run

    def _get_compiled(self, B: int, shards: int, iter_cap: int, ramps: bool):
        key = (B, shards, iter_cap, ramps)
        if key not in self._compiled:
            if shards > 1:
                if B % shards:
                    raise ValueError(
                        f"sharded solve needs B divisible by shard count "
                        f"(B={B}, shards={shards}); pad via ScenarioPack.shard")
                fn = jax.pmap(self._make_run(B // shards, iter_cap, ramps))
            else:
                fn = jax.jit(self._make_run(B, iter_cap, ramps))
            self._compiled[key] = fn
        return self._compiled[key]

    # -- host-side argument marshalling ------------------------------------
    def level_args(self, args_np: dict, B: int, ramps: bool) -> list:
        """Group per-process packed inputs by topology level (host-side,
        numpy): resource inputs stack to ``(Lr, Lp, rows, P)``, and for
        edge-free levels the data ceilings are fully pre-composed
        (``compose_scalar``) and pre-stacked to ``(nC, Lp, rows, P)`` — so
        the compiled program re-runs NO loop-invariant composition ops.
        Levels with edge-fed deps keep their static slots pre-composed per
        process (``"ceil"``) and compose only the edges in-trace.
        """
        arity = 4 if ramps else 3
        largs = []
        for ls in self.spec.levels:
            la: dict = {"C": None, "IR": None, "ceil": {}}
            if ls.Lr:
                slots = []
                for li in range(ls.Lr):
                    row = []
                    for ps in ls.procs:
                        if li < len(ps.res_names):
                            row.append(
                                args_np[ps.name]["res"][ps.res_names[li]])
                        else:
                            row.append(_ZERO_FN)
                    slots.append(row)
                la["IR"] = _np_pad_stack(slots, arity=3)
            static_slots: dict[tuple[int, str], tuple] = {}
            for pi, ps in enumerate(ls.procs):
                a = args_np[ps.name]
                for dep in ps.data_names:
                    if dep in ps.edges:
                        continue
                    if dep in a.get("ceil", {}):
                        static_slots[(pi, dep)] = a["ceil"][dep]
                    else:
                        tr = a["data"][dep]
                        inner = BPL(*(np.asarray(x, np.float64) for x in tr))
                        static_slots[(pi, dep)] = compose_scalar(
                            ps.req_fns[dep], inner).arrays()
            if ls.static_ceils:
                per = []
                for pi, ps in enumerate(ls.procs):
                    cl = [static_slots[(pi, dep)] for dep in ps.data_names]
                    if not cl:
                        cl = [(np.zeros((1, 1)), np.full((1, 1), ps.p_end),
                               np.zeros((1, 1)))]
                    while len(cl) < ls.nC:
                        cl.append((np.zeros((1, 1)),
                                   np.full((1, 1), _PAD_CEIL),
                                   np.zeros((1, 1))))
                    per.append(cl)
                la["C"] = _np_pad_stack([[per[pi][ci] for pi in range(len(per))]
                                         for ci in range(ls.nC)], arity=arity)
            else:
                la["ceil"] = {f"{pi}.{dep}": tr
                              for (pi, dep), tr in static_slots.items()}
            largs.append(la)
        return largs

    def _pad_level_args(self, largs: list, B: int, Bp: int) -> list:
        """Pad every full-batch rows axis to Bp by replicating the last
        scenario (single-row broadcast arrays are left alone)."""
        def pad(a):
            a = np.asarray(a)
            if a.ndim < 2 or a.shape[-2] != B:
                return a
            last = a[..., -1:, :]
            return np.concatenate([a] + [last] * (Bp - B), axis=-2)

        return jax.tree_util.tree_map(pad, largs)

    def device_args(self, largs: list, B: int, shards: int = 1) -> list:
        """Numpy level pytree -> device pytree (reshaped ``(D, ..., B/D, P)``
        when sharded; single-row broadcast arrays are replicated per device).
        Quadratic batches ship their ``c2`` plane as a 4th array — the tuple
        arity is part of the pytree structure the trace specializes on."""
        def put(a):
            a = np.asarray(a, np.float64)
            if shards > 1:
                D = shards
                if a.shape[-2] == 1:
                    a = np.broadcast_to(a, (D,) + a.shape)
                else:
                    lead = a.shape[:-2]
                    a = a.reshape(lead + (D, B // D, a.shape[-1]))
                    a = np.moveaxis(a, -3, 0)
            return jnp.asarray(a)

        return jax.tree_util.tree_map(put, largs)

    # -- the public solve ---------------------------------------------------
    def solve(self, args, B: int, *, shards: int = 1,
              cache: dict | None = None,
              scenario_ids: list[int] | None = None,
              ramps: bool = False,
              ) -> dict[str, BatchProcResult]:
        """Run the compiled sweep; adaptively double the iteration budget on
        overflow (recompiling) up to ``MAX_ITER_CAP``.

        ``ramps`` is the static degree switch (see :func:`_solve_level`):
        pass True when any packed resource input has a non-zero slope or any
        packed function a quadratic plane — the pack computes this once
        (:attr:`ScenarioPack.ramps`).

        With ``shards > 1`` the scenario axis is padded up to a multiple of
        the shard count (padding rows replicate the last scenario, are
        solved redundantly, and are sliced away) and split across local
        devices with ``jax.pmap``.
        """
        shards = int(shards)
        ramps = bool(ramps)
        if shards > jax.local_device_count():
            raise ValueError(
                f"shards={shards} but only {jax.local_device_count()} JAX "
                "device(s) are visible; on CPU set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
                "JAX initializes")
        Bp = -(-B // shards) * shards
        key = ("dev", Bp, shards)
        if cache is not None and key in cache:
            dev = cache[key]
        else:
            with TraceAnnotation("bm.engine.stage"):
                if callable(args):
                    args = args()
                largs = self.level_args(args, B, ramps)
                if Bp != B:
                    largs = self._pad_level_args(largs, B, Bp)
            with TraceAnnotation("bm.engine.put"):
                dev = self.device_args(largs, Bp, shards)
            if cache is not None:
                cache[key] = dev
        pkey = (Bp, shards, ramps)
        # a new batch size starts at the largest budget proven for another
        # (event depth is the workflow's more than the batch's): one compile,
        # no ladder, and no down-ratchet to recompile for
        known = [c for (_b, sh, r), c in self._proven_caps.items()
                 if (sh, r) == (shards, ramps)]
        first = not known
        cap = self._proven_caps.get(pkey, max(known, default=self.iter_cap))
        while True:
            # one ladder step: dispatch, the wait for the device, and the
            # overflow flag's readback
            with TraceAnnotation("bm.engine.call"):
                fn = self._lookup_aot(Bp, shards, cap, ramps, dev)
                if fn is None:
                    self._record_call(Bp, shards, cap, ramps, dev)
                    fn = self._get_compiled(Bp, shards, cap, ramps)
                out = fn(dev)
                overflow = bool(np.asarray(out["__overflow__"]).any())
            if not overflow:
                break
            cap *= 2
            if cap > MAX_ITER_CAP:
                raise IterationLadderExhausted(
                    f"jax engine exceeded {MAX_ITER_CAP} lockstep iterations; "
                    "use the numpy backend for this workload")
        if first:
            # one-time down-ratchet: the record buffers, progress pieces and
            # share scans all scale with the iteration budget, so the FIRST
            # successful solve tightens the proven cap to the actual event
            # depth (next power of two).  The next same-shape solve pays one
            # recompile and every re-sweep after runs with tight buffers;
            # later deeper packs still double back up through the overflow
            # ladder (the key is set, so no second down-ratchet can thrash).
            actual = max((int(np.asarray(out[ls.procs[0].name]
                                         ["iterations"]).max())
                          for ls in self.spec.levels), default=1)
            cap = min(cap, 1 << max(actual - 1, 0).bit_length())
        self._proven_caps[pkey] = cap
        with TraceAnnotation("bm.engine.fetch"):
            results = self._wrap(out, B, shards, scenario_ids)
        # every process of a level reports the level's trip count
        self.level_steps += sum(results[ls.procs[0].name].iterations
                                for ls in self.spec.levels)
        return results

    def _wrap(self, out, B: int, shards: int,
              scenario_ids: list[int] | None = None,
              ) -> dict[str, BatchProcResult]:
        """Finish times, shares and trip counts in one ``jax.device_get``;
        progress stays on the device until something reads it
        (:class:`_ProgressFetch`: the MC and what-if paths never do)."""
        def host(a):
            if shards > 1:  # (D, Bp/D, ...) -> (Bp, ...) -> drop padding
                a = a.reshape((-1,) + a.shape[2:])
            return a[:B]

        procs, levels = self.spec.procs, self.spec.levels
        # every process of a level reports the level's trip count
        got = jax.device_get({
            "finish": [out[ps.name]["finish"] for ps in procs],
            "share": [out[ps.name]["share"] for ps in procs],
            "iterations": [out[ls.procs[0].name]["iterations"]
                           for ls in levels]})
        self._count_fetch(got)
        iterations = {ps.name: int(it.max())
                      for ls, it in zip(levels, got["iterations"])
                      for ps in ls.procs}
        lazy = _ProgressFetch(self, {ps.name: out[ps.name]["progress"]
                                     for ps in procs}, host)
        results: dict[str, BatchProcResult] = {}
        for ps, finish, share in zip(procs, got["finish"], got["share"]):
            finish, share = host(finish), host(share)
            # gate-never-finishes: same error surface as the numpy engine;
            # t_start is re-derived from the gate finishes (not shipped back)
            t0 = np.zeros(B)
            for g in ps.gate_names:
                gf = results[g].finish
                if not np.all(np.isfinite(gf)):
                    bad = int(np.argmin(np.isfinite(gf)))
                    if scenario_ids is not None:  # caller's index, not local
                        bad = scenario_ids[bad]
                    raise ValueError(f"gate {g!r} of {ps.name!r} never "
                                     f"finishes (scenario {bad})")
                t0 = np.maximum(t0, gf)
            K, L = len(ps.data_names), len(ps.res_names)
            kinds = ["data"] * K + ["resource"] * L
            names = list(ps.data_names) + list(ps.res_names)
            if not K:
                kinds, names = ["data"] + kinds, ["<none>"] + names
                share = np.concatenate([np.zeros((B, 1)), share], 1)
            results[ps.name] = BatchProcResult(
                name=ps.name, p_end=ps.p_end, t_start=t0, finish=finish,
                progress=functools.partial(lazy.get, ps.name), ceilings=None,
                factor_kinds=kinds, factor_names=names, share_seconds=share,
                iterations=iterations[ps.name])
        return results

    def _count_fetch(self, tree, progress: bool = False) -> None:
        """Count a fetched tree's bytes (any thread may fetch progress)."""
        nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))
        with self._fetch_lock:
            self.fetch_bytes += nbytes
            self.progress_fetches += progress

    # -- AOT export / adopt (durable plan artifacts) ------------------------
    def _lookup_aot(self, B: int, shards: int, cap: int, ramps: bool, dev):
        """An adopted AOT executable matching this exact call, or None."""
        entries = self._aot.get((B, shards, cap, ramps))
        if not entries:
            return None
        call = entries.get(_aval_sig(dev))
        if call is not None:
            self.aot_hits += 1
        return call

    def _record_call(self, B: int, shards: int, cap: int, ramps: bool,
                     dev) -> None:
        """Census the input avals of a jit call so export can AOT it.

        pmap executables (shards > 1) are not exportable — sharded solves
        stay on the jit path and a warm start re-traces them.
        """
        if shards != 1:
            return
        sigs = self._call_shapes.setdefault((B, shards, cap, ramps), {})
        sig = _aval_sig(dev)
        if sig not in sigs:
            sigs[sig] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), dev)

    def export_entries(self) -> list[dict]:
        """AOT-serialize (``jax.export``) every recorded single-device call
        signature; previously adopted blobs are carried forward so a
        re-export never loses executables this engine did not itself trace.

        Each entry: ``{"B", "iter_cap", "ramps", "sig", "blob"}``.
        """
        from jax import export as jax_export
        from jax._src.config import traceback_in_locations_limit

        entries = list(self._aot_blobs)
        have = {(e["B"], 1, e["iter_cap"], e["ramps"], _canon_sig(e["sig"]))
                for e in entries}
        for key in sorted(self._call_shapes):
            B, _shards, cap, ramps = key
            # a first solve records its call at the pre-ratchet budget; warm
            # solves start at the PROVEN cap, so that is the cap to export
            cap = self._proven_caps.get((B, _shards, ramps), cap)
            for sig, shapes in sorted(self._call_shapes[key].items()):
                if (B, 1, cap, ramps, sig) in have:
                    continue
                have.add((B, 1, cap, ramps, sig))
                # no traceback locations: they name the caller's source
                # lines, so one plan exported from two call sites would
                # serialize to different bytes (thread-local, this call only)
                with traceback_in_locations_limit(0):
                    exported = jax_export.export(
                        jax.jit(self._make_run(B, cap, ramps)))(shapes)
                entries.append({"B": int(B), "iter_cap": int(cap),
                                "ramps": bool(ramps), "sig": sig,
                                "blob": exported.serialize()})
        return entries

    def adopt_exported(self, entries: list) -> int:
        """Deserialize artifact entries into the AOT registry; returns how
        many executables were adopted.  Solves whose (B, iter_cap, ramps,
        aval signature) match run the stored program — zero new traces."""
        from jax import export as jax_export

        adopted = 0
        for e in entries:
            exported = jax_export.deserialize(e["blob"])
            key = (int(e["B"]), 1, int(e["iter_cap"]), bool(e["ramps"]))
            self._aot.setdefault(key, {})[_canon_sig(e["sig"])] = exported.call
            self._aot_blobs.append(e)
            adopted += 1
        return adopted

    def proven_caps_rows(self) -> list[tuple]:
        """Proven iteration budgets as portable rows (B, shards, ramps, cap)
        for the artifact manifest."""
        return [(int(B), int(sh), bool(r), int(cap))
                for (B, sh, r), cap in sorted(self._proven_caps.items())]

    def adopt_proven_caps(self, rows) -> None:
        """Install manifest cap rows so warm solves start at the proven
        budget (``first=False``: no second down-ratchet recompile)."""
        for B, sh, r, cap in rows:
            self._proven_caps.setdefault((int(B), int(sh), bool(r)), int(cap))


def _aval_sig(tree) -> tuple:
    """Hashable (treedef, leaf shape/dtype) signature of an input pytree —
    exactly what jit specializes on, so also the AOT-executable match key.
    Works on concrete arrays and on ``jax.ShapeDtypeStruct`` trees."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(int(d) for d in leaf.shape), str(leaf.dtype))
                  for leaf in leaves))


def _canon_sig(sig) -> tuple:
    """Re-canonicalize a signature that round-tripped through an artifact
    (tuples may have become lists)."""
    treedef, leaves = sig
    return (str(treedef),
            tuple((tuple(int(d) for d in shape), str(dtype))
                  for shape, dtype in leaves))


# ---------------------------------------------------------------------------
# trace instrumentation: "cut ops not flops" as a tracked number
# ---------------------------------------------------------------------------

def _jaxpr_counts(jaxpr) -> tuple[int, int, int]:
    """``(while_loops, body_eqns, total_eqns)`` of a jaxpr, recursively.

    ``body_eqns`` sums the equation counts inside every ``while`` body —
    the per-iteration dispatch cost the level-fused engine minimizes;
    ``total_eqns`` counts every equation at every nesting depth.
    """
    try:
        from jax.extend.core import ClosedJaxpr
    except ImportError:  # older jax
        from jax.core import ClosedJaxpr

    def subjaxprs(eqn):
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, (list, tuple)):
                for u in v:
                    if isinstance(u, ClosedJaxpr):
                        yield u.jaxpr

    whiles = body = total = 0
    for eqn in jaxpr.eqns:
        total += 1
        if eqn.primitive.name == "while":
            whiles += 1
            bw, _bb, bt = _jaxpr_counts(eqn.params["body_jaxpr"].jaxpr)
            whiles += bw
            body += bt  # bt already counts nested bodies exactly once
            total += bt
            cw, cb, ct = _jaxpr_counts(eqn.params["cond_jaxpr"].jaxpr)
            total += ct
        else:
            for sub in subjaxprs(eqn):
                sw, sb, stot = _jaxpr_counts(sub)
                whiles += sw
                body += sb
                total += stot
    return whiles, body, total


def trace_report(plan, pack, *, iter_cap: int | None = None) -> dict:
    """Deterministic op-count report of the compiled re-sweep trace.

    Returns ``while_loops`` (one per topology level), ``body_eqns`` (total
    jaxpr equations inside the while bodies — the per-iteration dispatch
    cost), ``total_eqns`` (all equations at any depth) and ``hlo_lines``
    (unoptimized StableHLO op lines from ``jit(run).lower``).  Everything is
    machine-independent, so benchmarks can gate on it like a timing.
    """
    eng = getattr(plan, "_jax_engine", None) or JaxSweepEngine(plan)
    B = pack.B_batched
    largs = eng.level_args(pack.host_args(), B, pack.ramps)
    cap = iter_cap or eng._proven_caps.get((B, 1, pack.ramps), eng.iter_cap)
    run = eng._make_run(B, cap, pack.ramps)
    jaxpr = jax.make_jaxpr(run)(largs)
    whiles, body, total = _jaxpr_counts(jaxpr.jaxpr)
    hlo = jax.jit(run).lower(largs).as_text()
    hlo_lines = sum(1 for ln in hlo.splitlines() if " = " in ln)
    return {"while_loops": whiles, "body_eqns": body, "total_eqns": total,
            "hlo_lines": hlo_lines}
