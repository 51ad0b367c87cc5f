"""Property tests for the piecewise-QUADRATIC batched function class.

The tentpole contract: piecewise-linear (non-negative) resource inputs make
progress pieces quadratic, and the batched engines solve them in closed form
— eval / min / compose / first-crossing all agree with the exact scalar
substrate, and full sweeps agree with the scalar ``core.solver`` oracle
(``backend="loop"``), including near-degenerate quadratic discriminants and
the tangency tie-break (cap meeting the ceiling slope exactly).
"""

import numpy as np
import pytest

from repro import sweep
from repro.core import PPoly
from repro.core.ppoly import first_pos_root
from repro.core.solver import solve_euler
from repro.sweep.plin import BPL, compose_scalar

from test_sweep import _assert_match, _random_workflow, _single

RNG = np.random.default_rng


# ------------------------------------------------------ algebra vs scalar ----
def _random_quad_monotone(rng, n_pieces=4):
    """Monotone nondecreasing piecewise-quadratic function (continuous)."""
    xs = np.concatenate([[0.0], np.sort(rng.uniform(1.0, 50.0, n_pieces - 1))])
    coeffs, val = [], float(rng.uniform(0, 5))
    for i in range(n_pieces):
        ln = (xs[i + 1] - xs[i]) if i + 1 < n_pieces else 10.0
        c1 = float(rng.uniform(0, 5))
        c2 = float(rng.uniform(0, 0.5)) if rng.random() < 0.7 else 0.0
        coeffs.append([val, c1, c2])
        val = val + c1 * ln + c2 * ln * ln
    return PPoly(xs, coeffs)


def test_bpl_quadratic_eval_matches_scalar():
    rng = RNG(0)
    fns = [_random_quad_monotone(rng) for _ in range(24)]
    b = BPL.from_ppolys(fns)
    assert b.c2 is not None and b.max_degree() == 2
    ts = rng.uniform(-2.0, 70.0, (24, 17))
    exact = np.stack([f(ts[i]) for i, f in enumerate(fns)])
    np.testing.assert_allclose(b.eval_right(ts), exact, rtol=1e-12, atol=1e-12)


def test_bpl_quadratic_first_crossing_matches_scalar():
    rng = RNG(1)
    fns = [_random_quad_monotone(rng) for _ in range(40)]
    b = BPL.from_ppolys(fns)
    ys = rng.uniform(0.0, 400.0, 40)
    got = b.first_at_or_above(ys)
    exact = np.array([f.first_time_at_or_above(float(y), 0.0)
                      for f, y in zip(fns, ys)])
    both = np.isfinite(got) & np.isfinite(exact)
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(exact))
    np.testing.assert_allclose(got[both], exact[both], rtol=1e-9, atol=1e-9)


def test_bpl_quadratic_compose_matches_scalar():
    rng = RNG(2)
    fns = [_random_quad_monotone(rng) for _ in range(12)]
    outer = PPoly.pwlinear([0.0, 60.0, 150.0], [0.0, 120.0, 165.0])
    comp = compose_scalar(outer, BPL.from_ppolys(fns))
    ts = rng.uniform(0.0, 70.0, (12, 21))
    exact = np.stack([PPoly.compose(outer, f)(ts[i]) for i, f in enumerate(fns)])
    np.testing.assert_allclose(comp.eval_right(ts), exact, rtol=1e-9, atol=1e-9)


def test_scalar_minimum_with_quadratics_matches_samples():
    rng = RNG(3)
    for _ in range(8):
        fns = [_random_quad_monotone(rng, 3) for _ in range(3)]
        m, seg = PPoly.minimum(fns)
        ts = rng.uniform(0.0, 60.0, 200)
        exact = np.min(np.stack([f(ts) for f in fns]), 0)
        np.testing.assert_allclose(m(ts), exact, rtol=1e-9, atol=1e-9)
        assert seg[0][1] in range(3)


# ------------------------------------------------ stable quadratic formula ----
def test_first_pos_root_near_degenerate_discriminant():
    """Double roots and nearly-touching parabolas: the stable q-branch must
    not lose the root to cancellation, and a parabola whose peak stops just
    short of zero must report no root."""
    # (u - r)^2 = 0: exact double root at r, over many magnitudes
    r = np.array([1e-6, 1e-3, 1.0, 1e3, 1e6])
    u = first_pos_root(np.ones(5), -2.0 * r, r * r)
    np.testing.assert_allclose(u, r, rtol=1e-6)
    # peak epsilon short of the axis: no real root
    eps = 1e-9
    u = first_pos_root(np.array([-1.0]), np.array([2.0]),
                       np.array([-1.0 - eps]))  # -(u-1)^2 - eps
    assert not np.isfinite(u[0])
    # tiny leading coefficient: degrades gracefully to the linear root
    u = first_pos_root(np.array([1e-300]), np.array([2.0]), np.array([-8.0]))
    np.testing.assert_allclose(u, [4.0], rtol=1e-9)
    # exact linear case
    u = first_pos_root(np.zeros(1), np.array([2.0]), np.array([-8.0]))
    np.testing.assert_allclose(u, [4.0])


def test_first_crossing_at_tangent_level():
    """A piece rising to TOUCH the query level exactly (disc == 0)."""
    # f(u) = 10 - (5 - u)^2 on [0, 5], then flat 10: touches 10 at u=5
    f = PPoly(np.array([0.0, 5.0]), [np.array([-15.0, 10.0, -1.0]),
                                     np.array([10.0])])
    b = BPL.from_ppolys([f])
    got = b.first_at_or_above(np.array([10.0]))
    assert got[0] == pytest.approx(5.0, abs=1e-6)
    # a level epsilon above the tangent point is only reached by the flat
    # piece's tolerance band; far above, never
    assert not np.isfinite(b.first_at_or_above(np.array([11.0]))[0])


# ----------------------------------------------- engines vs scalar oracle ----
def _ramp_scenarios(rng, wf, b):
    """Randomized in-class resource overrides: ramps, starvation ramps,
    ramps with jumps, constants."""
    out = []
    for i in range(b):
        ov = {}
        for pn, allocs in wf.resource_alloc.items():
            for res in allocs:
                style = rng.random()
                if style < 0.3:
                    fn = PPoly.constant(float(rng.uniform(0.2, 8.0)))
                elif style < 0.7:  # continuous ramp chain
                    ts = np.sort(rng.uniform(1.0, 120.0, 2))
                    ys = rng.uniform(0.0, 8.0, 4)
                    fn = PPoly.pwlinear([0.0, *ts, ts[1] + 20.0], ys)
                elif style < 0.85:  # ramp down to exactly 0, then step back
                    t0 = float(rng.uniform(5.0, 40.0))
                    y0 = float(rng.uniform(1, 6))
                    fn = PPoly([0.0, t0, t0 + float(rng.uniform(1, 30))],
                               [[y0, -y0 / t0], [0.0],
                                [float(rng.uniform(1, 6))]])
                else:  # ramp with a jump discontinuity
                    t0 = float(rng.uniform(2.0, 50.0))
                    fn = PPoly([0.0, t0],
                               [[float(rng.uniform(0.0, 3)),
                                 float(rng.uniform(0, 0.3))],
                                [float(rng.uniform(2, 9)),
                                 float(rng.uniform(0, 0.2))]])
                ov[(pn, res)] = fn
        out.append(sweep.Scenario(label=f"s{i}", resource_inputs=ov))
    return out


@pytest.mark.parametrize("seed", range(8))
def test_randomized_ramp_sweeps_match_scalar(seed):
    rng = RNG(seed)
    wf = _random_workflow(rng)
    scs = _ramp_scenarios(rng, wf, 8)
    rb = wf.compile().sweep(scs, backend="numpy")
    assert set(rb.backends) == {"batched"}
    _assert_match(rb, wf.compile().sweep(scs, backend="loop"))


@pytest.mark.parametrize("seed", [2, 7])
def test_randomized_ramp_sweeps_match_jax(seed):
    rng = RNG(seed)
    wf = _random_workflow(rng)
    scs = _ramp_scenarios(rng, wf, 6)
    plan = wf.compile()
    pack = plan.prepare(scs)
    assert pack.ramps
    rj = plan.sweep(pack, backend="jax")
    assert set(rj.backends) == {"jax"}
    _assert_match(rj, plan.sweep(scs, backend="numpy"))


def test_hypothesis_property_quadratic_sweep_matches_scalar():
    """Deeper property test when hypothesis is available (CI installs it)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None, derandomize=True)
    def run(seed):
        rng = RNG(seed)
        wf = _random_workflow(rng)
        scs = _ramp_scenarios(rng, wf, 4)
        _assert_match(wf.compile().sweep(scs, backend="numpy"),
                      wf.compile().sweep(scs, backend="loop"))

    run()


def test_quadratic_data_input_batched():
    """Degree-2 data inputs are in-class too (fig-4's 0.2t + 0.11t^2 feed)."""
    wf = _single(PPoly.constant(800.0))
    wf.external_data["dl"]["file"] = PPoly(np.array([0.0]),
                                           [np.array([0.0, 0.2, 0.11])])
    scs = [sweep.Scenario(label=f"r{r}",
                          resource_inputs={("dl", "link"): PPoly.constant(r)})
           for r in (0.3, 2.0, 800.0)]
    rb = wf.compile().sweep(scs, backend="numpy")
    assert set(rb.backends) == {"batched"}
    _assert_match(rb, wf.compile().sweep(scs, backend="loop"))


def test_tangency_tiebreak_matches_euler():
    """Regression: at cap(t) == ceiling-slope(t) with the cap falling, the
    resource binds immediately — both the scalar solver and the batched
    engines once followed the ceiling to the next breakpoint instead."""
    n = 1000.0
    wf = _single(PPoly.constant(10.0), n)
    # data arrives along a decelerating quadratic; the link rate ramps DOWN
    # through the exact ceiling-slope tangency
    wf.external_data["dl"]["file"] = PPoly(
        np.array([0.0]), [np.array([0.0, 40.0, -0.18])])
    ramp = PPoly.pwlinear([0.0, 80.0], [40.0, 0.0])
    scs = [sweep.Scenario(label="t", resource_inputs={("dl", "link"): ramp})]
    rb = wf.compile().sweep(scs, backend="numpy")
    rl = wf.compile().sweep(scs, backend="loop")
    _assert_match(rb, rl)
    proc = wf.processes["dl"]
    ts, ps, fin = solve_euler(proc, {"file": wf.external_data["dl"]["file"]},
                              {"link": ramp}, t_end=300.0, dt=1e-3)
    assert np.isfinite(rb.finish["dl"][0]) == np.isfinite(fin)
    if np.isfinite(fin):
        assert rb.finish["dl"][0] == pytest.approx(fin, abs=0.05)
