"""CPU checks of the benchmark's own pieces: the deployment copies against
the program's scalar solver and fused engine, the generator, the sampler
copy, and the refusal to run without a TPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_harness.py

``montage_3x4`` is held to the scalar solver only: its fused sweep disagrees
with the scalar solver (a process with two or more edge-fed inputs), which
PERF.md records as a program fault; ``witness_multi_edge.py`` prints it.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import deploy  # noqa: E402
import reference  # noqa: E402
import sampler  # noqa: E402
import traffic as gen  # noqa: E402

RTOL = 1e-9


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic_file(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def scenarios_for(name, n, seed=5):
    if name == "paper_fig5":
        t = traffic_file("fig7_whatif_open")
        mix = gen.Mix(t, config(name))
        rng = np.random.default_rng(seed)
        return [mix.scenario(i % len(mix.kinds), rng) for i in range(n)]
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        x = float(rng.uniform(0.5, 2.0))
        ov = {f"{t}_{k}.cpu": ("scale", x) for k in range(12)
              for t in ("mProjectPP", "mBackground")}
        if i % 2:
            ov.update({f"mProjectPP_{k}.stagein": ("scale", float(rng.uniform(0.25, 2)))
                       for k in range(12)})
        out.append(ov)
    return out


def rows(rep, i):
    return [rep.makespans[i]] + [rep.finish[n][i] for n in rep.order]


def assert_agrees(rep, refs):
    for i, want in enumerate(refs):
        got = rows(rep, i)
        exp = [want["makespan"]] + [want["finish"][n] for n in rep.order]
        np.testing.assert_allclose(got, exp, rtol=RTOL)


@pytest.mark.parametrize("name", ["paper_fig5", "montage_3x4"])
def test_reference_matches_scalar_solver(name):
    cfg = config(name)
    plan = deploy.build_workflow(cfg).compile()
    ovs = scenarios_for(name, 4)
    specs = [deploy.program_scenario(o, deploy.data_keys(cfg)) for o in ovs]
    rep = plan.sweep(specs, backend="loop")
    ref = reference.Reference(cfg)
    assert_agrees(rep, [ref.solve(o) for o in ovs])


def test_fused_sweep_matches_reference_and_scalar_solver():
    cfg = config("paper_fig5")
    plan = deploy.build_workflow(cfg).compile()
    ovs = scenarios_for("paper_fig5", 4)
    specs = [deploy.program_scenario(o, deploy.data_keys(cfg)) for o in ovs]
    fused = plan.sweep(plan.prepare(specs), backend="jax")
    assert set(fused.backends) == {"jax"} and not fused.fallback_reasons
    ref = reference.Reference(cfg)
    assert_agrees(fused, [ref.solve(o) for o in ovs])
    loop = plan.sweep(specs, backend="loop")
    np.testing.assert_allclose(fused.makespans, loop.makespans, rtol=RTOL)


def test_float32_reference_is_rejected_by_the_limits():
    cfg = config("paper_fig5")
    lim = traffic_file("fig7_whatif_open")["check"]["limits"]["finish_rel"]
    ref, ref32 = reference.Reference(cfg), reference.Reference(cfg, np.float32)
    gap = max(abs(float(ref32.solve(o)["makespan"]) - r["makespan"]) / r["makespan"]
              for o in scenarios_for("paper_fig5", 64)
              for r in [ref.solve(o)])
    assert gap > lim


@pytest.mark.parametrize("seed", [1, 3_000_000_019])
def test_open_loop_gives_every_seed_the_same_work(seed):
    t, cfg = traffic_file("fig7_whatif_open"), config("paper_fig5")
    a = gen.open_loop(t, cfg, seed, 20.0)
    b = gen.open_loop(t, cfg, seed + 1, 20.0)
    assert len(a) == len(b) == round(t["rate_per_s"] * 20)
    assert sorted(len(r[1]) for r in a) == sorted(len(r[1]) for r in b)
    dues = [r[0] for r in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] <= 20.0
    assert all(1 <= len(r[1]) <= 64 for r in a)
    link = sum(1 for r in a for sc in r[1] if "dl1.link" in sc)
    assert link == sum(1 for r in b for sc in r[1] if "dl1.link" in sc)
    assert gen.open_loop(t, cfg, seed, 20.0)[5] == a[5]


def test_bursts_raise_the_arrival_rate():
    t, cfg = traffic_file("fig7_whatif_open"), config("paper_fig5")
    dues = np.array([r[0] for r in gen.open_loop(t, cfg, 9, 40.0)])
    counts = np.histogram(dues, bins=np.arange(0, 40.5, 0.5))[0]
    assert counts.max() > 2.5 * np.median(counts)


def test_warm_batches_reach_every_bucket():
    t, cfg = traffic_file("fig7_whatif_open"), config("paper_fig5")
    sizes = [len(b) for b in gen.warm_batches(t, cfg, 1, 256)]
    assert sorted(set(sizes)) == [1, 2, 4, 8, 16, 32, 64, 128, 256]
    assert sizes.count(1) == 5         # the all-kinds batch twice, 4 kinds
    assert sizes.count(256) >= 2


def test_mc_seeds_are_disjoint_and_fit_32_bits():
    big = 3_000_000_019
    win, warm = gen.mc_seeds(big, 50), gen.mc_seeds(big, 2, warm=True)
    assert not set(win) & set(warm) and max(win + warm) < 2 ** 31


def test_sampler_copy_matches_the_service_sampler():
    from repro.analysis.uncertainty import sample_spec

    cfg = config("paper_fig5")
    t = traffic_file("paper_mc10k")
    dk = deploy.data_keys(cfg)
    plan = deploy.build_workflow(cfg).compile()
    got = sample_spec(plan, deploy.program_mc_spec(t["dists"], dk), 500,
                      seed=2_147_483_647)
    want = sampler.draws(t["dists"], dk, 500, 2_147_483_647)
    assert set(got.values) == set(want)
    for k, v in want.items():
        np.testing.assert_array_equal(got.values[k], v)


@pytest.mark.parametrize("workload", ["paper_fig5.whatif_open",
                                      "paper_fig5.mc10k"])
def test_refuses_to_run_without_a_tpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        workload, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout and "needs 1 TPU" in p.stderr
