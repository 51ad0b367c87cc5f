"""The fused engine's lookups select by mask, and select exactly.

Every lookup of the compiled sweep that indexes an array by a per-row index
(a piece of a ceiling or resource table, a ceiling or resource slot, a
requirement-table row, the next valid record) is a one-hot mask and a
reduction, not an XLA ``gather``: on the TPU a gather fetches one element
at a time.  Each helper is held here to ``jnp.take_along_axis`` (kept only
in this file, as the oracle), BIT FOR BIT: sorted-with-duplicates tables
with ``PAD_START`` padding, ``-0.0`` and infinities among the values, and
queries exactly at breakpoints, ``TIME_TOL`` either side of them, before
the first piece and past the last.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import cap_space
from repro.analysis.optimize import _DiffObjective
from repro.analysis.pack import ThetaMap
from repro.analysis.scenarios import override, ramp_resource
from repro.configs.paper_workflow import compile_paper_plan
from repro.core.ppoly import TIME_TOL
from repro.kernels.ppoly_eval.ref import PAD_START
from repro.sweep import jax_engine as je


def take(a, i, axis=-1):
    """``a`` at index ``i`` (one rank less) along ``axis``, by gather."""
    return jnp.take_along_axis(jnp.asarray(a),
                               jnp.expand_dims(jnp.asarray(i), axis),
                               axis).squeeze(axis)


def same_bits(got, want) -> None:
    got, want = np.atleast_1d(got), np.atleast_1d(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(got.view(np.uint8), want.view(np.uint8)), \
        (got, want)


# ------------------------------------------------------------- tables ----
def _table(arity: int):
    """``(4, P)`` piece tables: duplicate starts (a superseded piece), a
    ``PAD_START`` tail, and coefficients holding ``-0.0`` and ``±inf``."""
    s = np.array([[0.0, 1.0, 1.0, 2.5, PAD_START, PAD_START],
                  [-1.0, 0.0, 3.0, 3.0, 3.0, 7.0],
                  [0.5, PAD_START, PAD_START, PAD_START, PAD_START,
                   PAD_START],
                  [0.0, 0.0, 0.0, 1e-9, 2.0, PAD_START]])
    rng = np.random.default_rng(7)
    planes = [s]
    for _ in range(arity - 1):
        c = rng.normal(size=s.shape)
        c[0, 1], c[1, 2], c[2, 0], c[3, 4] = -0.0, np.inf, -np.inf, -0.0
        planes.append(c)
    return tuple(planes)


def _queries(s):
    """Per row: every breakpoint, ``TIME_TOL`` either side of it, half of
    it, before the first start and far past the last."""
    rows = []
    for r in s:
        real = r[r < PAD_START * 0.5]
        q = np.concatenate([real, real - TIME_TOL, real + TIME_TOL,
                            real - 2 * TIME_TOL, real + 0.5 * TIME_TOL,
                            [real[0] - 1.0, real[-1] + 1e6, -0.0]])
        rows.append(q)
    n = max(len(q) for q in rows)
    return np.stack([np.pad(q, (0, n - len(q)), mode="edge") for q in rows])


def _idx(s, t, tol):
    """The oracle piece index: the last start ``<= t + tol``, else 0."""
    return np.maximum((s[:, None, :] <= t[..., None] + tol).sum(-1) - 1, 0)


def _oracle_eval(f, t, tol, out: str):
    """The piece evaluation with gathered coefficients and the engine's
    arithmetic (in jnp, so an ``inf * 0`` NaN comes out alike)."""
    s = np.broadcast_to(f[0][:, None, :], t.shape + (f[0].shape[-1],))
    i = _idx(f[0], t, tol)
    g = [take(np.broadcast_to(a[:, None, :], s.shape), i) for a in f]
    u = jnp.asarray(t) - g[0]
    q = g[3] if len(f) == 4 else jnp.zeros_like(g[1])
    val = (g[1] + (g[2] + q * u) * u) if len(f) == 4 else g[1] + g[2] * u
    if out == "value":
        return (val,)
    slope = g[2] + 2.0 * q * u if len(f) == 4 else g[2]
    if out == "value_slope":
        return val, slope
    return val, slope, q


def _case_eval(fn, tol, out, arity):
    f = _table(arity)
    t = _queries(f[0])
    fj = tuple(jnp.asarray(a[:, None, :]) for a in f)
    got = fn(fj, jnp.asarray(t))
    got = got if isinstance(got, tuple) else (got,)
    return got, _oracle_eval(f, t, tol, out)


def case_locate(arity):
    f = _table(arity)
    t = _queries(f[0])
    fj = tuple(jnp.asarray(a[:, None, :]) for a in f)
    hit, nb = je._locate(fj, jnp.asarray(t))
    i = _idx(f[0], t, TIME_TOL)
    want = [take(np.broadcast_to(a[:, None, :], hit.shape), i) for a in f]
    got = [je._pick(a, hit) for a in fj]
    s = np.broadcast_to(f[0][:, None, :], hit.shape)
    nb_want = np.where((s < PAD_START * 0.5) & ~(s <= t[..., None] + TIME_TOL),
                       s, np.inf).min(-1)
    return got + [nb], want + [nb_want]


def case_slots(arity):
    """``kstar`` / ``lstar`` over the leading slot axis, and a requirement
    row (``ri``, ``pbidx``) over the minor axis of a broadcast table."""
    rng = np.random.default_rng(3)
    V = rng.normal(size=(5, 3, 8))
    V[1, 0, :2], V[2, 1, 3], V[4, 2, 5] = -0.0, np.inf, -np.inf
    k = rng.integers(0, 5, size=(3, 8))
    rb = np.array([[[[0.0, 2.0, 2.0, np.inf, np.inf]]],
                   [[[-0.0, 1.0, 5.0, 9.0, np.inf]]]])       # (2, 1, 1, 5)
    r = rng.integers(0, 5, size=(2, 1, 8))
    got = [je._pick(jnp.asarray(V), je._onehot(jnp.asarray(k), 5, axis=0), 0),
           je._pick(jnp.asarray(rb), je._onehot(jnp.asarray(r), 5))]
    want = [take(V, k, 0),
            take(np.broadcast_to(rb, (2, 1, 8, 5)), r)]
    return got, want


def case_ints(arity):
    """Integer payloads (attribution codes), the dtype's least included."""
    A = np.array([[3, -1, 7, np.iinfo(np.int32).min, 0],
                  [0, 0, 2, 2, -5]], np.int32)
    i = np.array([[3], [4]])[:, 0]
    return [je._pick(jnp.asarray(A), je._onehot(jnp.asarray(i), 5))], \
        [take(A, i)]


def case_suffix(arity):
    """The record-slot fill of ``_assemble_progress`` / ``_aggregate_shares``:
    each slot takes the payload of the nearest valid slot at or after it."""
    rng = np.random.default_rng(11)
    R = 13
    M = rng.random((4, R)) < 0.4
    M[0] = False                    # a row with no valid slot
    M[1] = True
    idx = np.where(M, np.arange(R), R)
    pay = rng.normal(size=(4, R))
    pay[2, ::3] = -0.0
    pay[3, 1] = np.inf
    fill = PAD_START
    (got,) = je._suffix_argmin_take(
        jnp.asarray(idx), [(jnp.asarray(np.where(M, pay, fill)), fill)])
    nxt = np.minimum.accumulate(idx[:, ::-1], -1)[:, ::-1]
    src = np.concatenate([pay, np.full((4, 1), fill)], -1)
    return [got], [jnp.take_along_axis(jnp.asarray(src), jnp.asarray(nxt),
                                       -1)]


def case_compose(arity):
    """``outer(inner(t))`` for a three-piece outer: the outer piece under
    each value, picked by a static loop of selects, against
    ``searchsorted`` and integer indexing."""
    f = _table(arity)
    inner = (f[0],) + tuple(
        np.where(f[0] < PAD_START * 0.5,
                 np.abs(np.nan_to_num(a, posinf=3.0, neginf=0.0)), 0.0)
        for a in f[1:])
    o_s = np.array([0.0, 0.5, 2.0])
    outer = (o_s, np.array([0.0, 1.0, -0.0]), np.array([2.0, 0.0, 3.0]))
    B = inner[0].shape[0]
    got = je._compose(outer, tuple(jnp.asarray(a) for a in inner), B)
    # oracle: the same crossing columns, then the outer by searchsorted
    planes = tuple(jnp.asarray(a) for a in inner)
    for v in o_s[1:]:
        cross = je._first_at_or_above(planes, jnp.full(B, float(v)))
        cs = jnp.where(jnp.isfinite(cross), cross, PAD_START)
        cols = (je._eval_slope_quad_right(planes, cs) if arity == 4
                else je._eval_slope_right(planes, cs))
        planes = je._insert_col(planes, (cs,) + tuple(cols))
    S, V, SL = (np.asarray(a) for a in planes[:3])
    oi = np.maximum(np.searchsorted(o_s, V + TIME_TOL, side="right") - 1, 0)
    pad = S >= PAD_START * 0.5
    want = [S, np.where(pad, 0.0, outer[1][oi] + outer[2][oi] * (V - o_s[oi])),
            np.where(pad, 0.0, outer[2][oi] * SL)]
    if arity == 4:
        want.append(np.where(pad, 0.0, outer[2][oi] * np.asarray(planes[3])))
    return list(got), want


CASES = {
    "locate": case_locate,
    "eval_right": lambda a: _case_eval(je._eval_right, TIME_TOL,
                                       "value", a),
    "eval_left": lambda a: _case_eval(je._eval_left, -TIME_TOL,
                                      "value", a),
    "eval_slope_right": lambda a: _case_eval(je._eval_slope_right,
                                             TIME_TOL, "value_slope", a),
    "eval_slope_quad_right": lambda a: _case_eval(
        je._eval_slope_quad_right, TIME_TOL, "value_slope_quad", a),
    "slots": case_slots,
    "ints": case_ints,
    "suffix": case_suffix,
    "compose": case_compose,
}


@pytest.mark.parametrize("arity", [3, 4], ids=["linear", "quadratic"])
@pytest.mark.parametrize("helper", sorted(CASES))
def test_masked_selection_is_the_gather_bit_for_bit(helper, arity):
    got, want = CASES[helper](arity)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        same_bits(g, w)


# ------------------------------------------- differentiable makespan ----
#: ``make_diff_run``'s makespan and its gradient at the points the
#: finite-difference tests of ``test_optimize.py`` probe, as the engine
#: gave them when every lookup was an element gather
DIFF_POINTS = [
    (("task1.cpu", "dl1.link"), (1.31, 0.73), None,
     341.12126570061537, (-62.93339548977331, -350.24454466987993)),
    (("task1.cpu", "dl1.link"), (0.41, 1.0), None,
     453.0599520009205, (-642.4747174301012, -186.645317854579)),
    (("task1.cpu", "task2.cpu"), (1.37, 0.81), "ramp",
     1877.6430484549878, (-57.541691086365795, 0.0)),
]


@pytest.mark.parametrize("axes,theta,base,value,grad", DIFF_POINTS,
                         ids=["paper", "paper_early", "ramp"])
def test_diff_run_gradient_unchanged(axes, theta, base, value, grad):
    plan = compile_paper_plan(0.5)
    sc = (ramp_resource("dl1", "link", [0.0, 120.0], [1.6e6, 0.6e6],
                        label="ramp") if base == "ramp"
          else override(label="base"))
    space = cap_space(list(axes), lo=0.25, hi=4.0)
    f = _DiffObjective(plan, ThetaMap(plan, space.axes), plan.prepare([sc]),
                       1, None)
    v, g = f.value_grad(np.asarray(theta, np.float64)[None, :])
    same_bits(np.asarray(v[0]), np.float64(value))
    same_bits(np.asarray(g[0]), np.asarray(grad, np.float64))
