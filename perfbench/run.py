#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 perfbench/run.py --workload paper_fig5.whatif_open \\
        --seed 1234 --seconds 20 --trace 0

From the checkout root.  The cell (``BENCHMARK.json``'s ``workloads``)
names a deployment (``perfbench/configs/<config>.json``) and a traffic mix
(``perfbench/traffic/<traffic>.json``); the traffic file's ``loop`` picks
the load loop (:mod:`drivers`).  Set-up builds the service and warms every
shape the traffic can reach; the window then loads it for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
per-layer metrics, each read by ``perfbench/metrics/<name>.py``.  Every run
ends with the reference check (:mod:`check`); the numbers compared and their
limits are the last lines on standard error and the last key of the result.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import drivers  # noqa: E402


class Compiles:
    """XLA compile requests (persistent-cache hits included), counted from
    ``jax.monitoring`` as ``chip_smoke.py`` does."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0

        def on_duration(name: str, secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)


class Run:
    """What the per-layer readers may read (see ``metrics/``)."""

    def __init__(self, driver, compiles_in_window, trace=None, window=None):
        self.driver = driver
        self.stats0, self.stats1 = driver.stats0, driver.stats1
        self.window_compiles = compiles_in_window
        self.trace = trace
        self.window_ns = window

    def delta(self, key: str) -> int:
        return self.stats1[key] - self.stats0[key]


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, patch=None) -> tuple:
    """``(BENCHMARK.json, the cell, its configuration, its traffic)``;
    ``patch(traffic)`` may change the traffic (the CPU tests shrink it).

    A cell is looked for in ``BENCHMARK.json``, then among the cells held
    back from it (``deferred.json``: they run for the tools and the tests,
    and the benchmark never asks for them)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    held = json.loads((HERE / "deferred.json").read_text())["workloads"]
    cell = next((w for w in bench["workloads"] + held
                 if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r}")
    config = json.loads((HERE / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    if patch is not None:
        patch(traffic)
    return bench, cell, config, traffic


def use_cache() -> None:
    """Put the program on the path and its persistent compile cache on
    (every executable, however quick to compile, so set-up is steady)."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None, *, require_tpu: bool = True, patch=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench, cell, config, traffic = load_cell(args.workload, patch)
    except KeyError as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        print(f"run: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3

    use_cache()
    compiles = Compiles()

    span = jax.profiler.TraceAnnotation
    driver = drivers.DRIVERS[traffic["loop"]](config, traffic, args.seed,
                                              args.seconds, span)
    driver.setup()
    setup_compiles = compiles.n
    setup_s = time.time() - T_START

    trace_dir = HERE / ".trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no Python tracer: it slows the host about twofold, and every
        # per-layer metric is read in this run; idle gaps are named by the
        # benchmark's spans and the runtime's own host events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        ctx = jax.profiler.trace(str(trace_dir), profiler_options=opts)
    else:
        ctx = contextlib.nullcontext()
    with ctx:
        driver.window()
    window_compiles = compiles.n - setup_compiles

    dev = jax.devices()[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:cell["chips"]])
    driver.close()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    if args.trace:
        import tracing

        tr = tracing.load(str(trace_dir))
        lo, hi = tracing.window(tr)
        run = Run(driver, window_compiles, tr, (lo, hi))
        device["busy_s"] = tracing.busy_ns(tr, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = {"device_ops": tracing.top_ops(tr, lo, hi),
                     "idle_gaps": tracing.idle_gaps(tr, lo, hi)}
        for m in bench["per_layer"]:
            if applies(m, args.workload):
                v = load_reader(m["name"])(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(driver.end_to_end(), setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, args.workload) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    result = check.run(driver, config, traffic)
    correct = check.passed(result)
    for m in list(metrics):
        v = metrics[m]["value"]
        if v != v or v in (float("inf"), float("-inf")):
            del metrics[m]
            correct = False
    print(f"run: setup {setup_s:.3f}s, {setup_compiles} compile requests in "
          f"set-up ({compiles.seconds:.3f}s), {window_compiles} in the window",
          file=sys.stderr)
    for err in driver.errors[:3]:
        print(f"run: lost: {err}", file=sys.stderr)
    for name, (v, lim) in result.items():
        print(f"check {name} = {v:.6g} (limit {lim:.6g})", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": driver.attempted(),
           "failed": driver.failed(), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in result.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
