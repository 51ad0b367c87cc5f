#!/usr/bin/env python3
"""Bring-up smoke test of the analysis service on one TPU chip.

Run from the checkout root::

    python chip_smoke.py               # one chip: every serving phase
    python chip_smoke.py --four-chips  # sharded sweep + MC over 4 chips only

Everything runs in this one process (a chip belongs to one process) through
the public API, on ``jax.devices()[0]``:

1. service what-if: the 600 Fig. 7 scenarios from 64 concurrent clients,
   then a few ramped-link queries (linear and quadratic traces);
2. Monte Carlo: ``plan.mc(mc_spec(), n=10_000, seed=0)`` twice,
   bit-identical;
3. optimize: ``plan.optimize(space=fig7_space())`` reaches the Fig. 7
   optimum (frac 0.98, 206.227 s) in <= 50 evaluations;
4. online: ``svc.track`` plus a few ``ingest`` deltas;
5. warm start: ``plan.export`` -> ``load_plan`` -> sweep with zero new
   traces and bit-identical makespans;
6. curve queries: the three Pallas-backed ``Report`` queries, compiled.

Every phase is held to the host reference on the same scenarios (the numpy
engine, and the scalar solver on a sample) at the golden tolerance.  No
fallback may stand in for the chip: a non-``jax`` row, a degraded row, an
engine fallback, an artifact that re-traces, or a kernel in interpret mode
fails the run.  Wall times and compile counts are bring-up facts, not
benchmark numbers.  The last stdout line is the JSON verdict; it is printed
only when every check passed on a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

#: relative tolerance against the host reference: the golden pin of
#: tests/test_golden_paper_workflow.py
RTOL = 1e-9
#: the Pallas curve queries run compiled, never interpreted or on the jnp ref
KERNEL_FLAGS = {"use_pallas": True, "interpret": False}
#: seconds any one client waits for its answer
TIMEOUT = 900.0
#: the Fig. 7 optimum (frac 0.98), to the digits the paper row pins
FIG7_BEST_S = 206.227

#: executables JAX asked XLA for (persistent-cache hits included), the
#: seconds those requests took, and how many the persistent cache served
_XLA = {"compiles": 0, "seconds": 0.0, "cache_hits": 0}


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _count_compiles() -> None:
    import jax

    def on_duration(name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            _XLA["compiles"] += 1
            _XLA["seconds"] += secs

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            _XLA["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class Phase:
    """Prints wall time, XLA compiles and engine traces of one phase."""

    def __init__(self, name: str, traces=lambda: 0):
        self.name, self.traces = name, traces

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self.start = dict(_XLA)
        self.tr0 = self.traces()
        return self

    def __exit__(self, exc_type, *_exc) -> None:
        if exc_type is None:
            d = {k: v - self.start[k] for k, v in _XLA.items()}
            print(f"[smoke] {self.name}: wall={time.perf_counter() - self.t0:.1f}s"
                  f" xla_compiles={d['compiles']} ({d['seconds']:.1f}s,"
                  f" {d['cache_hits']} from the persistent cache)"
                  f" engine_traces={self.traces() - self.tr0}"
                  " (bring-up run, not a benchmark)", flush=True)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def rows(rep) -> np.ndarray:
    """Makespans and every process finish time of a report, one matrix."""
    return np.stack([rep.makespans] + [rep.finish[n] for n in rep.order])


def rel_err(got, want) -> float:
    """Largest relative error of ``got`` against ``want``; non-finite
    entries must match exactly."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    fin = np.isfinite(want)
    require(np.array_equal(got[~fin], want[~fin], equal_nan=True)
            and bool(np.isfinite(got[fin]).all()),
            "non-finite entries differ from the reference")
    if not fin.any():
        return 0.0
    return float(np.max(np.abs(got[fin] - want[fin])
                        / np.maximum(np.abs(want[fin]), np.finfo(float).tiny)))


def hold(phase: str, what: str, err: float, tol: float = RTOL) -> None:
    print(f"[smoke] {phase}: max rel err vs {what} = {err:.3e} "
          f"(bound {tol:g})", flush=True)
    require(err <= tol, f"{phase}: {what} error {err:.3e} exceeds {tol:g}")


def on_chip(rep, what: str) -> None:
    """Every row ran on the fused engine; nothing degraded or fell back."""
    require(set(rep.backends) == {"jax"},
            f"{what}: rows left the fused engine: {sorted(set(rep.backends))}")
    require(rep.engine_fallback is None,
            f"{what}: engine fallback: {rep.engine_fallback}")


def against_host(phase: str, plan, scenarios, chip: np.ndarray,
                 every: int = 1) -> np.ndarray:
    """Hold chip rows to the numpy engine on every scenario and to the scalar
    solver on every ``every``-th one; returns the numpy rows."""
    ref = rows(plan.sweep(scenarios, backend="numpy"))
    hold(phase, "numpy engine", rel_err(chip, ref))
    pick = list(range(0, len(scenarios), every))
    loop = rows(plan.sweep([scenarios[i] for i in pick], backend="loop"))
    hold(phase, f"scalar solver ({len(pick)} rows)",
         rel_err(chip[:, pick], loop))
    return ref


def no_degradation(svc, phase: str) -> None:
    snap = svc.snapshot()
    require(snap["degraded"] == 0 and snap["restarts"] == 0,
            f"{phase}: service degraded={snap['degraded']} "
            f"restarts={snap['restarts']}")


# ---------------------------------------------------------------------------
# one chip: every serving phase
# ---------------------------------------------------------------------------

def single_chip(*, n_whatif: int = 600, n_clients: int = 64,
                n_mc: int = 10_000) -> None:
    from repro.analysis import (AnalysisService, ArtifactWarning, load_plan,
                                ramp_resource)
    from repro.configs.paper_workflow import (build_workflow,
                                              compile_paper_plan, fig7_space,
                                              mc_spec, sweep_scenarios)

    wf = build_workflow(0.5)
    svc = AnalysisService(wf, backend="jax")
    plan = svc.compile(wf)
    traces = lambda: svc.snapshot()["cold_traces"]  # noqa: E731
    fracs = np.linspace(0.02, 0.98, n_whatif)
    scs = sweep_scenarios(fracs)

    # -- 1. service what-if: 64 concurrent clients, then ramped links -------
    with Phase("what-if", traces):
        parts = np.array_split(np.arange(n_whatif), n_clients)
        barrier = threading.Barrier(n_clients)

        def client(idx):
            barrier.wait()
            return svc.query([scs[i] for i in idx], timeout=TIMEOUT)

        with ThreadPoolExecutor(n_clients) as pool:
            reps = [f.result() for f in
                    [pool.submit(client, idx) for idx in parts]]
        for rep in reps:
            on_chip(rep, "what-if")
        chip = np.concatenate([rows(r) for r in reps], axis=1)
        grid_host = against_host("what-if", plan, scs, chip, every=40)
        no_degradation(svc, "what-if")

    with Phase("ramped what-if", traces):
        ramps = [ramp_resource("dl2", "link", [0.0, 200.0], [4e6 * f, 0.5e6],
                               label=f"ramp{f:.2f}")
                 for f in (0.3, 0.55, 0.8, 1.0)]
        futs = [svc.submit(r) for r in ramps]
        reps = [f.result(TIMEOUT) for f in futs]
        for rep in reps:
            on_chip(rep, "ramped what-if")
        against_host("ramped what-if", plan, ramps,
                     np.concatenate([rows(r) for r in reps], axis=1))
        no_degradation(svc, "ramped what-if")

    # -- 2. Monte Carlo, twice, bit-identical ---------------------------------
    with Phase("monte carlo", traces):
        mcs = [plan.mc(mc_spec(), n=n_mc, seed=0, backend="jax")
               for _ in range(2)]
        for mc in mcs:
            on_chip(mc.report, "monte carlo")
            require(mc.fallback_count == 0 and mc.degraded_count == 0,
                    f"monte carlo: fallbacks={mc.fallback_count} "
                    f"degraded={mc.degraded_count}")
        require(np.array_equal(rows(mcs[0].report), rows(mcs[1].report))
                and np.array_equal(mcs[0].report.share_seconds,
                                   mcs[1].report.share_seconds),
                "monte carlo: two runs with one seed differ")
        against_host("monte carlo", plan, mcs[0].scenarios,
                     rows(mcs[0].report), every=max(n_mc // 16, 1))
        print(f"[smoke] monte carlo: {mcs[0].quantiles()}", flush=True)

    # -- 3. optimize: the Fig. 7 optimum in <= 50 evaluations -----------------
    with Phase("optimize", traces):
        opt = plan.optimize(space=fig7_space(), max_evals=50)
        theta = float(opt.theta[0])
        gi = int(np.argmin(grid_host[0]))
        print(f"[smoke] optimize: theta={theta:.4f} value={opt.value:.6f}s "
              f"evals={opt.evals} sweeps={opt.sweeps}", flush=True)
        require(opt.evals <= 50, f"optimize: {opt.evals} evals (cap 50)")
        require(abs(theta - 0.98) <= fracs[1] - fracs[0]
                and abs(theta - fracs[gi]) <= fracs[1] - fracs[0],
                f"optimize: theta {theta} is not the Fig. 7 optimum 0.98")
        require(abs(opt.value - FIG7_BEST_S) <= 5e-4,
                f"optimize: value {opt.value} != {FIG7_BEST_S} s")
        # opt.report is the host (numpy) sweep of the optimum's scenario
        hold("optimize", "numpy engine at the optimum",
             rel_err(opt.value, opt.report.makespans[0]))
        loop = plan.sweep(opt.report.scenarios, backend="loop")
        hold("optimize", "scalar solver at the optimum",
             rel_err(opt.value, loop.makespans[0]))

    # -- 4. online: tracked session + monitoring deltas ------------------------
    with Phase("online", traces):
        live = svc.track(sweep_scenarios([0.5]))
        deltas = [None, {"dl1.link": np.float64(0.8)}, {"task1.cpu": 0.7},
                  {"dl2.link": 1.1}]
        for d in deltas:
            rep = live.ingest(d, timeout=TIMEOUT)
            on_chip(rep, "online")
            against_host("online", plan, live.pack.scenarios, rows(rep))
        no_degradation(svc, "online")

    # -- 5. warm start from a plan artifact ------------------------------------
    author = compile_paper_plan(0.5)
    pack = author.prepare(scs)
    with Phase("warm start: author", lambda: author._jax_engine.trace_count
               if author._jax_engine else 0):
        author.sweep(pack, backend="jax")            # compiles, ratchets cap
        rep_a = author.sweep(pack, backend="jax")    # at the proven cap
    with tempfile.TemporaryDirectory() as d, warnings.catch_warnings():
        warnings.simplefilter("error", ArtifactWarning)  # no silent re-trace
        path = author.export(Path(d) / "paper.bmplan")
        loaded = load_plan(path, strict=True)
        eng = loaded._jax_engine
        require(eng is not None, "warm start: artifact carried no engine")
        with Phase("warm start: loaded", lambda: eng.trace_count):
            rep_w = loaded.sweep(loaded.prepare(scs), backend="jax")
    on_chip(rep_w, "warm start")
    require(eng.trace_count == 0 and eng.aot_hits > 0,
            f"warm start: trace_count={eng.trace_count} "
            f"aot_hits={eng.aot_hits}")
    require(np.array_equal(rows(rep_w), rows(rep_a))
            and np.array_equal(rep_w.share_seconds, rep_a.share_seconds),
            "warm start: loaded sweep differs from the authoring sweep")
    hold("warm start", "numpy engine", rel_err(rows(rep_w), grid_host))
    print(f"[smoke] warm start: trace_count=0 aot_hits={eng.aot_hits}",
          flush=True)

    # -- 6. the three curve queries, compiled ------------------------------------
    with Phase("curve queries"):
        curve_queries(rep_w)

    svc.close()
    no_degradation(svc, "end")
    print(f"[smoke] service snapshot: {svc.snapshot()}", flush=True)


def _away_from_breaks(starts: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """(B, T) mask of query times not within float32 slack of a breakpoint."""
    gap = np.min(np.abs(ts[None, :, None] - starts[:, None, :]), axis=-1)
    return gap > 1e-3 * np.maximum(1.0, np.abs(ts))[None, :]


def curve_queries(rep) -> None:
    """``sample_progress``, ``data_ceiling`` and ``kernel_finish_times`` on a
    batched report, held to the float64 host evaluation at float32
    tolerance (the bound tests/test_kernel_ppoly_eval.py uses)."""
    ts = np.linspace(0.0, 1.1 * float(np.max(rep.makespans)), 128)
    worst = 0.0
    for proc in rep.order:
        res = rep.proc_results[proc]
        got = rep.sample_progress(proc, ts, **KERNEL_FLAGS)
        want = res.progress.eval_right(np.broadcast_to(ts, (rep.B, len(ts))))
        ok = _away_from_breaks(res.progress.starts, ts)
        worst = max(worst, float(np.max(np.abs(got - want)[ok]
                                        / np.maximum(1.0, np.abs(want[ok])))))
        fin = rep.kernel_finish_times(proc, **KERNEL_FLAGS)
        hold("curve queries", f"engine finish times ({proc})",
             rel_err(fin, rep.finish[proc]), tol=1e-4)
    hold("curve queries", "host progress (sample_progress)", worst, tol=5e-4)
    worst, wrong = 0.0, 0
    for proc in rep.order:
        ceils = list(rep.proc_results[proc].ceilings)
        vals, arg = rep.data_ceiling(proc, ts, **KERNEL_FLAGS)
        tq = np.broadcast_to(ts, (rep.B, len(ts)))
        host = np.stack([c.eval_right(tq) for c in ceils])       # (F, B, T)
        ok = np.ones(vals.shape, bool)
        for c in ceils:
            ok &= _away_from_breaks(c.starts, ts)
        want = host.min(0)
        big = np.abs(want) < 1e29                    # finite ceilings only
        m = ok & big
        if m.any():
            worst = max(worst, float(np.max(
                np.abs(vals - want)[m] / np.maximum(1.0, np.abs(want[m])))))
        srt = np.sort(host, axis=0)
        clear = m if len(ceils) == 1 else m & (
            srt[1] - srt[0] > 1e-3 * np.maximum(1.0, np.abs(srt[0])))
        wrong += int(np.sum(arg[clear] != host.argmin(0)[clear]))
    hold("curve queries", "host ceilings (data_ceiling)", worst, tol=5e-4)
    require(wrong == 0, f"curve queries: {wrong} argmin attributions differ")


# ---------------------------------------------------------------------------
# four chips: the sharded scenario axis against device 0
# ---------------------------------------------------------------------------

def _mem(dev) -> dict:
    return dev.memory_stats() or {}


def device_shares(devs, before: list, after: list) -> None:
    """Each device held its shard: its allocation count and peak grew."""
    for d, b, a in zip(devs, before, after):
        print(f"[smoke] four chips: device {d.id}: peak_bytes_in_use "
              f"{b.get('peak_bytes_in_use')} -> {a.get('peak_bytes_in_use')}, "
              f"num_allocs {b.get('num_allocs')} -> {a.get('num_allocs')}",
              flush=True)
        require(a.get("num_allocs", 0) > b.get("num_allocs", 0)
                or a.get("peak_bytes_in_use", 0) > b.get("peak_bytes_in_use", 0),
                f"four chips: device {d.id} shows no share of the work")


def four_chips(devs, *, n_whatif: int = 600, n_mc: int = 10_000) -> None:
    from repro.configs.paper_workflow import (compile_paper_plan, mc_spec,
                                              sweep_scenarios)

    n = len(devs)
    plan = compile_paper_plan(0.5)
    pack = plan.prepare(sweep_scenarios(np.linspace(0.02, 0.98, n_whatif)))
    traces = lambda: plan._jax_engine.trace_count if plan._jax_engine else 0  # noqa: E731
    with Phase(f"sharded over {n}", traces):
        before = [_mem(d) for d in devs]
        sharded = plan.sweep(pack.shard(n), backend="jax")
        after = [_mem(d) for d in devs]
        device_shares(devs, before, after)
        mc_sh = plan.mc(mc_spec(), n=n_mc, seed=0, backend="jax", shards=n)
    with Phase("device 0", traces):
        single = plan.sweep(pack, backend="jax")
        mc_1 = plan.mc(mc_spec(), n=n_mc, seed=0, backend="jax")
    for rep in (sharded, single, mc_sh.report, mc_1.report):
        on_chip(rep, "four chips")
    require(np.array_equal(rows(sharded), rows(single))
            and np.array_equal(sharded.share_seconds, single.share_seconds),
            "four chips: sharded sweep differs from device 0")
    require(np.array_equal(rows(mc_sh.report), rows(mc_1.report))
            and np.array_equal(mc_sh.report.share_seconds,
                               mc_1.report.share_seconds),
            "four chips: sharded Monte Carlo differs from device 0")
    print(f"[smoke] four chips: sweep ({n_whatif} rows) and Monte Carlo "
          f"({n_mc} draws) bit-identical to device 0", flush=True)


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sweep and Monte Carlo sharded over 4 "
                         "chips, compared with device 0")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}")
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        sys.exit(f"chip_smoke: needs {need} TPU chips, found {len(devs)}")
    print(f"[smoke] device: {devs[0].device_kind} x{len(devs)}", flush=True)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(f"[smoke] compile cache: {use_compile_cache()}", flush=True)
    _count_compiles()
    if args.four_chips:
        four_chips(devs[:4])
    else:
        single_chip()
    # CPU-only dry-run tools force a 512-device CPU platform at import
    for mod in ("repro.launch.dryrun", "repro.perfmodel.breakdown"):
        require(mod not in sys.modules, f"{mod} was imported")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
