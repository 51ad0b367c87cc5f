"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout), mirroring:

* Fig. 4  — single-process progress/bottleneck example
* Fig. 7  — 600-prioritization sweep (one batched ``repro.sweep`` pass),
            predictions vs DES ground truth
* sweep   — batched engine vs looped scalar solver, us/scenario at B=600
* resweep — prepared-pack re-sweeps on one compiled plan: jax fused engine
            vs numpy lockstep vs the legacy re-compile-every-call shim
* mc      — B=10k Monte Carlo draws of the paper workflow's uncertainty
            model as one fused sweep: quantiles + attribution probabilities
* Fig. 8  — bottleneck structure at 50 % / 95 %
* Sect. 6 — analysis runtime: BottleMod vs discrete-event simulation,
            1.1 GB vs 100 GB input (the headline scaling claim)
* beyond-paper: BottleMod step model over a dry-run cell; ppoly_eval batched
  kernel vs naive loop; roofline table summary

CLI: positional substrings filter benchmarks by name; ``--quick`` runs a
small-B smoke subset (numpy + jax backends, CI-friendly); ``--compare
OLD.json`` prints per-row speedups against a previous ``BENCH_sweep.json``
and exits non-zero on a >20 % regression, so perf PRs carry their own
before/after evidence.
"""

from __future__ import annotations

import json
import pathlib
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
RESULTS = ROOT / "results"

#: set by --quick: shrink batch sizes / rep counts to CI-smoke scale
QUICK = False

#: rows with us_per_call above old * (1 + threshold) fail --compare
REGRESSION_THRESHOLD = 0.20


def _time(fn, n=5, warmup=1):
    """Min-of-n wall time (us): scheduling noise on a shared box only ever
    ADDS time, so the min is the robust per-call cost (keeps the --compare
    regression gate from tripping on load spikes)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_fig4_example():
    from repro.core import DataDep, PPoly, Process, ResourceDep, solve
    N = 1000.0
    proc = Process("fig4",
                   data={"data0": DataDep.stream(N, N),
                         "data1": DataDep.stream(N, N),
                         "data2": DataDep.stream(N, N)},
                   resources={"res0": ResourceDep.stream(80.0, N),
                              "res1": ResourceDep.stream(120.0, N),
                              "res2": ResourceDep.stream(60.0, N)},
                   total_progress=N).identity_output()
    din = {"data0": PPoly.linear(0.0, 12.0),
           "data1": PPoly.step([0.0, 40.0], [200.0, 1000.0]),
           "data2": PPoly(np.array([0.0]), [np.array([0.0, 0.2, 0.11])])}
    rin = {"res0": PPoly.constant(1.0),
           "res1": PPoly.pwlinear([0.0, 50.0], [0.8, 2.0]),
           "res2": PPoly.constant(0.9)}
    res = solve(proc, din, rin)
    us = _time(lambda: solve(proc, din, rin), n=20)
    segs = len(res.segments)
    return ("fig4_progress_example", us,
            f"finish={res.finish_time:.1f}s segments={segs} events={res.iterations}")


def bench_fig7_sweep():
    """Fig. 7's 600 prioritizations, evaluated as ONE batched sweep."""
    from repro.configs.paper_workflow import (
        build_workflow, measure_makespan, sweep_scenarios,
    )
    fracs = np.linspace(0.02, 0.98, 600)
    base = build_workflow(0.5)
    scenarios = sweep_scenarios(fracs)
    t0 = time.perf_counter()
    res = base.compile().sweep(scenarios, backend="batched")
    per_analysis_us = (time.perf_counter() - t0) / len(fracs) * 1e6
    pred = res.makespan
    # DES ground truth at every 20th point
    sel = fracs[::20]
    des = np.array([measure_makespan(f)[0] for f in sel])
    prd = pred[::20]
    base_ref = build_workflow(0.5, recipe="refined")
    ref = base_ref.compile().sweep(sweep_scenarios(sel),
                                   backend="batched").makespan
    err_paper = float(np.mean(np.abs(prd - des) / des))
    err_refined = float(np.mean(np.abs(ref - des) / des))
    two = base.compile().sweep(sweep_scenarios([0.50, 0.93]),
                               backend="batched").makespan
    m50, m93 = float(two[0]), float(two[1])
    best_i, best_label, best_ms = res.top_k(1)[0]
    (RESULTS / "benchmarks").mkdir(parents=True, exist_ok=True)
    np.savez(RESULTS / "benchmarks" / "fig7.npz", fracs=fracs, pred=pred,
             sel=sel, des=des, refined=ref)
    return ("fig7_600_prioritizations_batched", per_analysis_us,
            f"improvement_50_to_93={100 * (1 - m93 / m50):.1f}% (paper:32%) "
            f"err_paper_recipe={100 * err_paper:.1f}% err_refined={100 * err_refined:.2f}% "
            f"best={best_label}({best_ms:.1f}s)")


def bench_sweep_batched_vs_loop():
    """Acceptance row: batched sweep vs looped scalar solver at B=600."""
    from repro.configs.paper_workflow import build_workflow, sweep_scenarios
    plan = build_workflow(0.5).compile()
    B = 60 if QUICK else 600
    scenarios = sweep_scenarios(np.linspace(0.02, 0.98, B))
    res = plan.sweep(scenarios, backend="batched")  # warm caches
    t0 = time.perf_counter()
    res = plan.sweep(scenarios, backend="batched")
    us_batched = (time.perf_counter() - t0) / B * 1e6
    n_loop = 60  # the loop backend is too slow to run all 600 here
    t0 = time.perf_counter()
    res_loop = plan.sweep(scenarios[::B // n_loop], backend="loop")
    us_loop = (time.perf_counter() - t0) / len(res_loop.makespan) * 1e6
    err = float(np.max(np.abs(res.makespan[::B // n_loop] - res_loop.makespan)
                       / res_loop.makespan))
    return ("sweep_batched_vs_loop", us_batched,
            f"B={B}: batched={us_batched:.0f}us/scen loop={us_loop:.0f}us/scen "
            f"speedup={us_loop / us_batched:.0f}x max_rel_err={err:.1e}")


def bench_compile_once_resweep():
    """Acceptance row: repeated RE-SWEEPS of a prepared scenario pack on ONE
    compiled plan — the fused jax lockstep engine — vs the per-call paths it
    amortizes away: ``plan.sweep(list)`` (re-resolves + re-packs every call,
    numpy lockstep) and the legacy ``sweep.analyze`` shim (additionally
    re-compiles the workflow every call).

    All paths are measured interleaved (rotating order) and summarized by
    their minima — scheduling noise on a shared box only ever ADDS time, so
    with enough rounds the min is the robust per-call cost.  The headline
    ``us_per_call`` is the prepared-pack re-sweep at B=600 (B=48 in
    ``--quick``), i.e. the cost of asking the same compiled plan one more
    batch of what-if questions.
    """
    import warnings

    from repro import sweep
    from repro.configs.paper_workflow import build_workflow, sweep_scenarios
    base = build_workflow(0.5)
    parts = []
    us_pack_main = 0.0
    sizes = ((48, 10),) if QUICK else ((600, 30), (32, 40))
    for B, n in sizes:
        scenarios = sweep_scenarios(np.linspace(0.02, 0.98, B))
        plan = base.compile()
        # compile/prepare are timed warm and min-of-n like every other row:
        # the first call carries import, allocator, and (for prepare on a
        # fresh process) first-touch costs that are not the steady-state
        # cost a re-preparing caller pays — the old single-shot measurement
        # read 70ms at B=32 vs 11ms at B=600 purely from call order
        us_compile = _time(lambda: base.compile(), n=5)
        pack = plan.prepare(scenarios)
        us_prepare = _time(lambda: plan.prepare(scenarios), n=5)
        plan.sweep(pack)                            # warm (jit compile)
        plan.sweep(pack)                            # tight-budget recompile
        plan.sweep(scenarios)
        # the legacy shim is timed ON PURPOSE (it is the baseline this row
        # exists to beat); silence its DeprecationWarning in the hot loop
        def _legacy():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                sweep.analyze(base, scenarios)
        _legacy()
        tj, tp, tl = [], [], []
        rot = [(tj, lambda: plan.sweep(pack)),
               (tp, lambda: plan.sweep(scenarios)),
               (tl, _legacy)]
        for k in range(n):
            for sink, fn in rot[k % 3:] + rot[:k % 3]:
                t0 = time.perf_counter()
                fn()
                sink.append((time.perf_counter() - t0) * 1e6)
        us_pack, us_list, us_legacy = min(tj), min(tp), min(tl)
        if B == sizes[0][0]:
            us_pack_main = us_pack
        parts.append(
            f"B={B}: pack_resweep_jax={us_pack / 1e3:.2f}ms "
            f"plan.sweep_numpy={us_list / 1e3:.1f}ms "
            f"legacy_analyze={us_legacy / 1e3:.1f}ms "
            f"resweep_speedup_vs_list={us_list / us_pack:.1f}x "
            f"vs_legacy={us_legacy / us_pack:.1f}x "
            f"(compile={us_compile / 1e3:.2f}ms prepare={us_prepare / 1e3:.2f}ms, "
            "both once)")
    return ("compile_once_resweep", us_pack_main, " ".join(parts))


def bench_quadratic_resweep():
    """Quadratic-class acceptance row: prepared-pack re-sweeps where EVERY
    scenario carries a piecewise-linear (ramped) resource override — the
    degree-2 path (quadratic progress pieces, widened jax trace) must stay
    on the fused engines with zero scalar fallbacks."""
    import warnings

    from repro.analysis import ramp_resource
    from repro.configs.paper_workflow import build_workflow

    B = 24 if QUICK else 200
    plan = build_workflow(0.5).compile()
    scs = [ramp_resource("dl1", "link", [0.0, 120.0], [2e6 * f, 0.6e6],
                         label=f"ramp{f:.2f}")
           for f in np.linspace(0.3, 2.0, B)]
    pack = plan.prepare(scs)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any fallback warning fails the row
        res = plan.sweep(pack)              # warm (jit compile)
        assert res.fallback_indices == [], "quadratic sweep fell back"
        us_jax = _time(lambda: plan.sweep(pack), n=10)
        us_np = _time(lambda: plan.sweep(pack, backend="numpy"), n=5)
    return ("quadratic_ramp_resweep", us_jax,
            f"B={B} all-ramp overrides: jax={us_jax / 1e3:.2f}ms "
            f"numpy={us_np / 1e3:.1f}ms fallbacks=0 "
            f"(pw-linear resource class, quadratic progress pieces)")


def bench_optimize_paper_fig7():
    """Fig. 7 allocation search: the gradient optimizer vs the 600-point
    grid it replaces.  ``us_per_call`` is the wall time of one full
    ``plan.optimize`` run (including its jit traces — the cost a cold
    caller pays); the derived column carries the acceptance numbers: the
    optimizer must land on the grid argmax within one grid spacing, match
    its makespan to <= 1e-6 relative, and spend <= 50 candidate evals
    where the paper's grid spends 600."""
    from repro.configs.paper_workflow import (compile_paper_plan, fig7_space,
                                              sweep_scenarios)
    plan = compile_paper_plan(0.5)
    fracs = np.linspace(0.02, 0.98, 600)
    grid_ms = plan.sweep(sweep_scenarios(fracs), backend="batched").makespan
    gi = int(np.argmin(grid_ms))
    t0 = time.perf_counter()
    opt = plan.optimize(space=fig7_space(), max_evals=50)
    us = (time.perf_counter() - t0) * 1e6
    rel = abs(opt.value - float(grid_ms[gi])) / float(grid_ms[gi])
    assert opt.evals <= 50, f"optimizer spent {opt.evals} evals (cap 50)"
    assert abs(float(opt.theta[0]) - fracs[gi]) <= fracs[1] - fracs[0]
    assert rel <= 1e-6, f"optimum off the grid best by {rel:.1e} relative"
    return ("optimize_paper_fig7", us,
            f"evals={opt.evals} (grid:600) sweeps={opt.sweeps} "
            f"iters={opt.iters} theta={float(opt.theta[0]):.4f} "
            f"(grid:{fracs[gi]:.4f}) value={opt.value:.2f}s "
            f"rel_err_vs_grid={rel:.1e} converged={opt.converged}")


def bench_resweep_trace_ops():
    """Satellite: "cut ops not flops" as a tracked number — deterministic
    jaxpr/HLO size counters for the level-fused B=600 re-sweep trace.

    The ``us_per_call`` column carries the total equation count inside the
    ``while`` bodies (the per-iteration XLA dispatch cost the level-fused
    engine minimizes), so the ``--compare`` gate flags a >20 % op-count
    growth exactly like a timing regression — but machine-independently.
    The pre-level-fusion engine traced to 5 loops / 2141 body equations.
    """
    from repro.configs.paper_workflow import build_workflow, sweep_scenarios
    from repro.sweep.jax_engine import DEFAULT_ITER_CAP, trace_report

    plan = build_workflow(0.5).compile()
    pack = plan.prepare(sweep_scenarios(np.linspace(0.02, 0.98, 600)))
    rep = trace_report(plan, pack, iter_cap=DEFAULT_ITER_CAP)
    return ("resweep_trace_ops_b600", float(rep["body_eqns"]),
            f"while_loops={rep['while_loops']} body_eqns={rep['body_eqns']} "
            f"total_eqns={rep['total_eqns']} hlo_lines={rep['hlo_lines']} "
            "(deterministic trace counters; us_per_call column = loop-body "
            "equations, gated like a timing; pre-fusion: 5 loops/2141)")


def bench_sharded_resweep():
    """Satellite: prepared-pack re-sweep with the scenario axis pmap-sharded
    over every visible device, vs the single-device path on the same pack.

    On a 1-device box this reports an explicit skip row (informational,
    never gated); CI's second matrix entry runs the quick bench under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the sharded
    path is exercised — and its parity asserted — on every PR.
    """
    import jax

    n = jax.local_device_count()
    if n < 2:
        return ("sharded_resweep", None,
                "skipped: 1 JAX device visible — set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=4 before "
                "JAX initializes (CI's second matrix entry does)")
    from repro.configs.paper_workflow import build_workflow, sweep_scenarios

    B = 48 if QUICK else 600
    plan = build_workflow(0.5).compile()
    base_pack = plan.prepare(sweep_scenarios(np.linspace(0.02, 0.98, B)))
    pack = base_pack.shard(n)
    plan.sweep(pack)                                # warm (pmap compile)
    plan.sweep(pack)                                # tight-budget recompile
    us = _time(lambda: plan.sweep(pack), n=8)
    single = plan.sweep(base_pack)
    sharded = plan.sweep(pack)
    err = float(np.max(np.abs(sharded.makespans - single.makespans)))
    assert err == 0.0, f"sharded sweep diverged from single-device: {err}"
    return ("sharded_resweep", us,
            f"B={B} shards={n}: resweep={us / 1e3:.2f}ms "
            f"single_device_parity_maxdiff={err:.1e}")


def bench_serve_coalesced():
    """Tentpole row (ISSUE 6): 64 concurrent clients' what-if requests
    coalesced by the :class:`~repro.analysis.serve.AnalysisService` into ONE
    stacked fused sweep.

    Each round queues 64 single-scenario requests on a paused service, then
    releases the worker: the drain stacks all of them into one ``(64,)``
    fused call and resolves every client's future with its own rows.  The
    headline ``us_per_call`` is the best round's p50 per-request latency
    (min-of-n spirit: scheduling noise only ever adds time); p99 and
    requests/s ride along in the derived column.  The per-request cost is
    the amortized fused call — dozens of clients for roughly the price of
    one what-if.
    """
    from repro.analysis import scenarios as S
    from repro.analysis.serve import AnalysisService
    from repro.configs.paper_workflow import build_workflow

    plan = build_workflow(0.5).compile()
    N = 64
    queries = [S.scale_resource("task1", "cpu", [float(f)])
               for f in np.linspace(0.5, 4.0, N)]
    rounds = 3 if QUICK else 6
    best = None
    for _ in range(rounds + 1):  # +1 warmup round (jit compile)
        svc = AnalysisService(autostart=False)
        svc.compile(plan)  # warm engine shared via the plan itself
        done = [0.0] * N
        futs = []
        for i, scs in enumerate(queries):
            fut = svc.submit(scs, plan=plan)
            fut.add_done_callback(
                lambda _f, i=i: done.__setitem__(i, time.perf_counter()))
            futs.append(fut)
        t0 = time.perf_counter()
        svc.start()
        for fut in futs:
            fut.result(timeout=600)
        svc.close()
        snap = svc.snapshot()
        assert snap["sweeps"] == 1, f"expected ONE fused sweep: {snap}"
        assert snap["max_coalesced"] == N, snap
        lats = np.sort(np.asarray(done) - t0)
        wall = float(lats[-1])
        row = (float(np.quantile(lats, 0.5)), float(np.quantile(lats, 0.99)),
               N / wall)
        if best is None or row[0] < best[0]:
            best = row
    p50, p99, rps = best
    return ("serve_coalesced_b64", p50 * 1e6,
            f"clients={N} one fused sweep/round: p50={p50 * 1e3:.2f}ms "
            f"p99={p99 * 1e3:.2f}ms rps={rps:.0f} (best of {rounds} rounds, "
            "per-request result == sequential plan.sweep, gated by tests)")


def bench_serve_warmstart():
    """Durable-artifact row (ISSUE 10): time-to-first-report from a cold
    compile (full XLA trace) vs from an AOT plan artifact
    (:func:`~repro.analysis.artifacts.load_plan` — deserialize + execute,
    zero re-traces).

    A prior "serving process" authors the artifact once; each round then
    measures (a) ``build_workflow().compile()`` + first fused sweep and
    (b) ``load_plan(path)`` + the same sweep.  The headline ``us_per_call``
    is the best warm time; the derived column carries the cold time and
    the restart speedup.  Correctness is pinned inline the same way the
    tests pin it: the warm engine's ``trace_count`` must stay 0 with
    ``aot_hits >= 1``, and both paths must be bit-identical to the
    authoring sweep.
    """
    import tempfile

    from repro.analysis import load_plan
    from repro.configs.paper_workflow import build_workflow, sweep_scenarios

    fracs = [0.3, 0.5, 0.7, 0.9]
    rounds = 2 if QUICK else 4
    with tempfile.TemporaryDirectory() as d:
        author = build_workflow(0.5).compile()
        ref = author.sweep(author.prepare(sweep_scenarios(fracs)),
                           backend="jax")
        path = author.export(pathlib.Path(d) / "paper.bmplan")

        cold_best = warm_best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            plan = build_workflow(0.5).compile()
            rep_c = plan.sweep(plan.prepare(sweep_scenarios(fracs)),
                               backend="jax")
            cold_best = min(cold_best, time.perf_counter() - t0)

            t0 = time.perf_counter()
            loaded = load_plan(path)
            rep_w = loaded.sweep(loaded.prepare(sweep_scenarios(fracs)),
                                 backend="jax")
            warm_best = min(warm_best, time.perf_counter() - t0)

            eng = loaded._jax_engine
            assert eng.trace_count == 0, "warm start re-traced"
            assert eng.aot_hits >= 1
            np.testing.assert_array_equal(rep_c.makespans, ref.makespans)
            np.testing.assert_array_equal(rep_w.makespans, ref.makespans)
    return ("serve_warmstart", warm_best * 1e6,
            f"artifact load+first sweep {warm_best * 1e3:.0f}ms vs cold "
            f"compile+trace {cold_best * 1e3:.0f}ms -> "
            f"{cold_best / warm_best:.1f}x faster restart (B={len(fracs)}, "
            "0 re-traces, bit-identical, gated by tests)")


def bench_serve_degraded():
    """Chaos row (ISSUE 8): the coalesced 64-client batch with 4 poisoned
    rows — the non-finite guard re-runs them on the numpy reference twin.

    Same shape as ``serve_coalesced_b64`` but with a ``FaultPlan`` injecting
    NaN into 4 of the 64 stacked rows every sweep, so the p50/p99 include
    the degradation detection + ``pack.subset`` re-run + row merge.  The row
    asserts exactly 4 degraded rows per round before timing; the headline is
    the best round's p50 per-request latency, gated by ``--compare`` so the
    degraded path cannot silently regress (nor can supervision overhead —
    the healthy ``serve_coalesced_b64`` row is the control).
    """
    import warnings

    from repro.analysis import scenarios as S
    from repro.analysis.faults import FaultPlan
    from repro.analysis.serve import AnalysisService
    from repro.configs.paper_workflow import build_workflow

    plan = build_workflow(0.5).compile()
    N, poison = 64, (3, 17, 31, 45)
    queries = [S.scale_resource("task1", "cpu", [float(f)])
               for f in np.linspace(0.5, 4.0, N)]
    rounds = 3 if QUICK else 6
    best = None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)  # the degrade warning
        for _ in range(rounds + 1):  # +1 warmup round (jit compile)
            svc = AnalysisService(autostart=False,
                                  faults=FaultPlan(nan_rows=poison,
                                                   nan_sweep=None))
            svc.compile(plan)
            done = [0.0] * N
            futs = []
            for i, scs in enumerate(queries):
                fut = svc.submit(scs, plan=plan)
                fut.add_done_callback(
                    lambda _f, i=i: done.__setitem__(i, time.perf_counter()))
                futs.append(fut)
            t0 = time.perf_counter()
            svc.start()
            for fut in futs:
                fut.result(timeout=600)
            svc.close()
            snap = svc.snapshot()
            assert snap["sweeps"] == 1, f"expected ONE fused sweep: {snap}"
            assert snap["degraded"] == len(poison), snap
            lats = np.sort(np.asarray(done) - t0)
            row = (float(np.quantile(lats, 0.5)),
                   float(np.quantile(lats, 0.99)))
            if best is None or row[0] < best[0]:
                best = row
    p50, p99 = best
    return ("serve_degraded_b64", p50 * 1e6,
            f"clients={N} poisoned_rows={len(poison)} degraded="
            f"{len(poison)}/round: p50={p50 * 1e3:.2f}ms "
            f"p99={p99 * 1e3:.2f}ms (numpy re-run of poisoned rows riding "
            "one fused sweep, row parity gated by tests)")


def bench_mc_quantiles():
    """Tentpole row (ISSUE 7): ``plan.mc`` — B=10k Monte Carlo draws of the
    paper workflow's default uncertainty model analyzed as ONE fused sweep
    (B=1024 in ``--quick``).

    The row asserts the subsystem's contract before timing: every draw must
    route to the fused jax engine (one compiled call for the whole draw
    set, zero scalar fallbacks) — a routing regression would silently turn
    the 10k-draw query into a Python loop.  The headline ``us_per_call`` is
    one full ``plan.mc`` invocation (sample + pack + fused sweep +
    quantiles), min-of-n on a warm plan.
    """
    import warnings

    from repro.configs.paper_workflow import build_workflow, mc_spec

    B = 1024 if QUICK else 10_000
    plan = build_workflow(0.5).compile()
    spec = mc_spec()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any fallback warning fails the row
        mc = plan.mc(spec, n=B, seed=0)         # warm (jit compile)
        assert set(mc.report.backends) == {"jax"}, "draws left the fused path"
        assert mc.fallback_count == 0, "MC draws fell back to the scalar loop"
        us = _time(lambda: plan.mc(spec, n=B, seed=0), n=3)
    q = mc.quantiles()
    top = mc.attribution()[0]
    return ("mc_quantiles_b10k", us,
            f"B={B} draws one fused call: mc={us / 1e3:.0f}ms "
            f"({us / B:.0f}us/draw) p50={q['p50']:.0f}s p95={q['p95']:.0f}s "
            f"p99={q['p99']:.0f}s dominant={top.label}@{top.p_dominant:.0%} "
            "fallbacks=0")


def bench_fig8_structure():
    from repro.configs.paper_workflow import build_workflow
    from repro.core import bottleneck_report
    out = []
    us = None
    for frac in (0.5, 0.95):
        wf = build_workflow(frac)
        if us is None:
            us = _time(lambda: wf.analyze(), n=10)
        wr = wf.analyze()
        shares = {(b.process, b.name): b.fraction for b in bottleneck_report(wr)}
        dl2_link = shares.get(("dl2", "link"), 0.0)
        out.append(f"{int(frac * 100)}%:makespan={wr.makespan:.0f}s,dl2_link={dl2_link:.0%}")
    return ("fig8_bottleneck_structure", us, " ".join(out))


def bench_perf_vs_des():
    """Sect. 6: BottleMod runtime is independent of data size; DES scales."""
    from repro.configs.paper_workflow import VIDEO_BYTES, measure_makespan, predict_makespan
    us_small = _time(lambda: predict_makespan(0.5), n=10)
    us_big = _time(lambda: predict_makespan(0.5, video_bytes=VIDEO_BYTES * 90), n=10)
    t0 = time.perf_counter()
    _, ev_small = measure_makespan(0.5)
    des_small_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, ev_big = measure_makespan(0.5, video_bytes=VIDEO_BYTES * 10)  # 11 GB (100 GB extrapolated)
    des_big_s = time.perf_counter() - t0
    des_100g_s = des_big_s * 9.0  # linear in events (measured 10x, paper used 100 GB)
    return ("sect6_bottlemod_vs_des", us_small,
            f"bottlemod:1.1GB={us_small / 1e3:.1f}ms,100GB={us_big / 1e3:.1f}ms "
            f"des:1.1GB={des_small_s * 1e3:.0f}ms({ev_small}ev),"
            f"11GB={des_big_s * 1e3:.0f}ms({ev_big}ev),100GB~{des_100g_s:.1f}s "
            f"(paper: 20.0ms vs 32.8ms and 22.8ms vs 1137ms)")


def bench_stepmodel():
    """Beyond-paper: BottleMod prediction of a training step from a dry-run."""
    from repro.perfmodel.stepmodel import StepModelInputs, predict
    rec_path = RESULTS / "dryrun" / "rwkv6-1.6b_train_4k_single.json"
    if rec_path.exists():
        rec = json.loads(rec_path.read_text())
        per = rec["per_device"]
        inputs = StepModelInputs(flops_per_step=per["flops"],
                                 hbm_bytes_per_step=per["bytes"],
                                 coll_bytes_per_step=per["collective_bytes"],
                                 n_steps=100, data_rate_steps_per_s=2.0,
                                 ckpt_every=20, ckpt_bytes=4e9)
        src = "dryrun:rwkv6-1.6b"
    else:
        inputs = StepModelInputs(flops_per_step=4.4e13, hbm_bytes_per_step=1.9e12,
                                 coll_bytes_per_step=1.2e11, n_steps=100,
                                 data_rate_steps_per_s=2.0, ckpt_every=20, ckpt_bytes=4e9)
        src = "builtin"
    us = _time(lambda: predict(inputs), n=5)
    p = predict(inputs)
    top_gain = p.gains[0] if p.gains else ("-", "-", 0, 0)
    return ("stepmodel_bottlemod_predict", us,
            f"src={src} step={p.step_time_s * 1e3:.1f}ms bound={p.dominant()} "
            f"best_whatif={top_gain[0]}/{top_gain[1]}(+{top_gain[3]:.1f}s/100steps)")


def bench_ppoly_kernel():
    from repro.core import PPoly
    from repro.kernels.ppoly_eval import pack_ppolys, ppoly_eval
    rng = np.random.default_rng(0)
    fns = []
    for _ in range(256):
        xs = np.concatenate([[0.0], np.sort(rng.uniform(0.5, 50, 7))])
        fns.append(PPoly.pwlinear(xs, np.cumsum(rng.uniform(0, 10, 8))))
    starts, coeffs = pack_ppolys(fns)
    q = rng.uniform(0, 55, (256, 512)).astype(np.float32)
    out = ppoly_eval(starts, coeffs, q, use_pallas=False)  # jnp ref (vectorized)
    out.block_until_ready()
    us_vec = _time(lambda: ppoly_eval(starts, coeffs, q, use_pallas=False).block_until_ready(), n=5)
    t0 = time.perf_counter()
    _ = [f(q[i].astype(np.float64)) for i, f in enumerate(fns[:32])]
    us_loop = (time.perf_counter() - t0) / 32 * 256 * 1e6
    n_evals = 256 * 512
    return ("ppoly_eval_batched_kernel", us_vec,
            f"{n_evals} evals: vectorized={us_vec / 1e3:.1f}ms "
            f"python_loop~{us_loop / 1e3:.0f}ms speedup={us_loop / us_vec:.0f}x "
            f"(pallas kernel validated vs oracle in tests)")


def bench_roofline_summary():
    """Summarize dry-run roofline cells.  This row is informational, never
    timed: with no dryrun results it reports an explicit skip reason, and
    with results it reports the cell summary — either way ``us_per_call``
    stays ``None`` so ``--compare`` never gates on an I/O-bound number."""
    recs = []
    for p in sorted((RESULTS / "dryrun").glob("*.json")):
        r = json.loads(p.read_text())
        if r.get("status") == "ok" and not r.get("tag"):
            recs.append(r)
    if not recs:
        return ("roofline_cells", None,
                "skipped: no dryrun results under results/dryrun — run "
                "`python -m repro.launch.dryrun --all` to populate this row")
    doms = {}
    for r in recs:
        doms[r["roofline"]["dominant"]] = doms.get(r["roofline"]["dominant"], 0) + 1
    ok_single = sum(1 for r in recs if r["mesh"] == "single")
    ok_multi = sum(1 for r in recs if r["mesh"] == "multi")
    return ("roofline_cells", None,
            f"ok_cells single={ok_single} multi={ok_multi} dominant={doms}")


BENCHES = [
    bench_fig4_example,
    bench_fig7_sweep,
    bench_sweep_batched_vs_loop,
    bench_compile_once_resweep,
    bench_optimize_paper_fig7,
    bench_quadratic_resweep,
    bench_resweep_trace_ops,
    bench_sharded_resweep,
    bench_serve_coalesced,
    bench_serve_warmstart,
    bench_serve_degraded,
    bench_mc_quantiles,
    bench_fig8_structure,
    bench_perf_vs_des,
    bench_stepmodel,
    bench_ppoly_kernel,
    bench_roofline_summary,
]

#: DES-heavy rows skipped by --quick (they dominate wall time and do not
#: exercise the sweep backends the smoke run is for)
QUICK_SKIP = {"bench_fig7_sweep", "bench_perf_vs_des", "bench_stepmodel"}

#: machine-readable per-benchmark wall times, tracked across PRs
BENCH_JSON = ROOT / "BENCH_sweep.json"
#: --quick writes here instead, so CI smoke runs (and devs trying --quick)
#: never clobber the tracked full-run trajectory above
BENCH_QUICK_JSON = ROOT / "BENCH_quick.json"


def _host() -> str:
    """Provenance tag for recorded baselines (timings are machine-relative)."""
    import os
    import platform

    return f"{platform.node()}/{os.cpu_count()}cpu"


def compare_rows(old_rows: list[dict], new_rows: list[dict],
                 threshold: float = REGRESSION_THRESHOLD,
                 ) -> tuple[list[str], list[str]]:
    """Per-row speedup report between two BENCH_sweep row lists.

    Returns ``(report_lines, regressions)``; a row regresses when its new
    timing exceeds the old by more than ``threshold``.  Rows without a
    usable timing on either side (skipped, errored, or 0.0 placeholders)
    are reported but never gate.
    """
    old_by = {r["name"]: r for r in old_rows}
    lines = [f"{'row':<34}{'old_us':>12}{'new_us':>12}{'speedup':>9}  note"]
    regressions: list[str] = []
    for nr in new_rows:
        name = nr["name"]
        orow = old_by.get(name)
        nus = nr.get("us_per_call")
        ous = orow.get("us_per_call") if orow else None
        if orow is None:
            new_col = f"{nus:12.1f}" if nus else f"{'-':>12}"
            lines.append(f"{name:<34}{'-':>12}{new_col}{'-':>9}  new row")
            continue
        if not ous and not nus:
            # informational row on BOTH sides (e.g. roofline_cells' explicit
            # skip row): expected steady state, exit-0 — not a data gap
            lines.append(f"{name:<34}{'-':>12}{'-':>12}{'-':>9}  "
                         "informational (untimed on both sides)")
            continue
        if not ous or not nus:  # None or 0.0: nothing comparable
            lines.append(f"{name:<34}{'-':>12}{'-':>12}{'-':>9}  skipped "
                         "(no timing on one side)")
            continue
        speedup = ous / nus
        note = ""
        if nus > ous * (1.0 + threshold):
            note = f"REGRESSION (> {threshold:.0%} slower)"
            regressions.append(name)
        elif speedup >= 1.0 + threshold:
            note = "improved"
        lines.append(f"{name:<34}{ous:12.1f}{nus:12.1f}{speedup:8.2f}x  {note}")
    return lines, regressions


def main(argv: list[str] | None = None) -> None:
    """Run benchmarks, print CSV rows, record ``BENCH_sweep.json``, and
    optionally gate against a previous run (see module docstring)."""
    import argparse
    import sys

    global QUICK
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("filters", nargs="*",
                    help="only run benchmarks whose name contains a substring")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small B, skip DES-heavy rows")
    ap.add_argument("--compare", metavar="OLD_JSON",
                    help="compare against a previous BENCH_sweep.json and "
                         f"exit 1 on a >{REGRESSION_THRESHOLD:.0%} regression")
    args = ap.parse_args(argv)
    QUICK = args.quick
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    old_rows: list[dict] | None = None
    old_quick = False
    if args.compare:
        old_payload = json.loads(pathlib.Path(args.compare).read_text())
        old_rows = old_payload["rows"]
        old_quick = bool(old_payload.get("quick"))

    rows = []
    print("name,us_per_call,derived")
    for fn in BENCHES:
        if args.filters and not any(f in fn.__name__ for f in args.filters):
            continue
        if QUICK and fn.__name__ in QUICK_SKIP:
            continue
        try:
            import gc
            gc.collect()  # normalize allocator/GC state between rows
            name, us, derived = fn()
            if us is None:  # informational row: content, no gated timing
                print(f"{name},-,{derived}")
                rows.append({"name": name, "us_per_call": None,
                             "derived": derived})
            else:
                print(f"{name},{us:.1f},{derived}")
                rows.append({"name": name, "us_per_call": round(float(us), 1),
                             "derived": derived})
        except Exception as e:  # noqa: BLE001 — finish the other rows first
            print(f"{fn.__name__},NaN,ERROR:{type(e).__name__}:{e}")
            rows.append({"name": fn.__name__, "us_per_call": None,
                         "error": f"{type(e).__name__}: {e}"})
    # partial (filtered) runs must not clobber the tracked trajectory, and
    # --quick rows (small B) go to their own file for the same reason
    if not args.filters:
        payload = {"schema": 1, "rows": rows, "host": _host()}
        if QUICK:
            payload["quick"] = True
        target = BENCH_QUICK_JSON if QUICK else BENCH_JSON
        target.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"# wrote {target.name} ({len(rows)} rows)")
    errored = [r["name"] for r in rows if "error" in r]
    if old_rows is not None:
        lines, regressions = compare_rows(old_rows, rows)
        print(f"# --compare vs {args.compare}")
        for ln in lines:
            print("# " + ln)
        old_host = old_payload.get("host")
        if old_host and old_host != _host():
            # the gate still applies (min-of-n absorbs scheduler noise, not
            # hardware deltas) — make a cross-machine failure self-explaining
            print(f"# NOTE: baseline recorded on {old_host!r}, this run on "
                  f"{_host()!r}; absolute timings are "
                  "machine-relative — if rows regress with no plausible code "
                  "cause, refresh the baseline from this run's uploaded "
                  "artifact")
        if old_quick != QUICK:
            # quick rows use smaller B — timings are not comparable, so
            # report but never gate across quick/full runs
            print(f"# NOTE: quick/full mismatch (old quick={old_quick}, "
                  f"this run quick={QUICK}); regression gate skipped")
        elif regressions:
            print(f"# FAIL: {len(regressions)} row(s) regressed: "
                  f"{', '.join(regressions)}")
            sys.exit(1)
        else:
            print("# compare OK: no regressions")
    if errored:  # a crashed benchmark must fail CI, compare mode or not
        print(f"# FAIL: {len(errored)} benchmark(s) errored: "
              f"{', '.join(errored)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
