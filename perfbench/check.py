"""The comparison that decides ``correct``: what the timed path served,
against the plain reference (:mod:`reference`) on the same scenarios.

Numbers compared (each against a limit in the traffic file's ``check``):

* ``finish_rel``: largest relative gap of a makespan or a process finish
  time;
* ``share_rel``: largest gap of a process's data- or resource-limited
  seconds (bottleneck attribution), over the scenario's makespan;
* ``sampler_rel`` (Monte Carlo): largest relative gap of a sampled factor,
  over every draw of a checked call;
* ``quantile_rel`` (Monte Carlo): largest relative gap of a reported
  quantile against numpy's quantile of the served makespans;
* ``lost``: requests or calls that never came back, or raised;
* ``off_path``: served rows that the fused engine did not answer (degraded
  to the host twin, scalar fallbacks, another backend): those were not
  served by the path under test.

``control`` runs the same comparison with the reference in float32 in the
program's place (the precision one step below the float64 the engine
states); that must fail the limits.
"""

from __future__ import annotations

import numpy as np

import deploy
import reference
import sampler


def _rel(got, want) -> float:
    if np.isinf(want) or np.isinf(got):
        return 0.0 if got == want else np.inf
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-300)


def rows_gap(items, ref, got_of) -> tuple:
    """``items``: ``(overrides, served, row)``; ``got_of(overrides, served,
    row)`` gives the result to judge (the served row, or the control's)."""
    fin = share = 0.0
    for ov, served, row in items:
        want = ref.solve(ov)
        got = got_of(ov, served, row)
        fin = max(fin, _rel(got["makespan"], want["makespan"]))
        for n, f in want["finish"].items():
            fin = max(fin, _rel(got["finish"][n], f))
        for key, s in want["share"].items():
            share = max(share, abs(float(got["share"].get(key, 0.0)) - float(s))
                        / float(want["makespan"]))
    return fin, share


def served_row(_ov, served, row) -> dict:
    return {"makespan": served.makespans[row],
            "finish": {n: v[row] for n, v in served.finish.items()},
            "share": {k: v[row] for k, v in served.share.items()}}


def control_row(ref32):
    return lambda ov, _served, _row: ref32.solve(ov)


def whatif(driver, config, limits, k, control=False) -> dict:
    items = driver.to_check(k)
    ref = reference.Reference(config)
    got = control_row(reference.Reference(config, np.float32)) if control \
        else served_row
    fin, share = rows_gap(items, ref, got)
    return {"finish_rel": (fin, limits["finish_rel"]),
            "share_rel": (share, limits["share_rel"]),
            "lost": (float(len(driver.lost())), 0.0),
            "off_path": (float(driver.off_path()), 0.0)}


def mc(driver, config, traffic, limits, calls, per_call, control=False) -> dict:
    dkeys = deploy.data_keys(config)
    ref = reference.Reference(config)
    dt = np.float32 if control else np.float64
    got = control_row(reference.Reference(config, dt)) if control \
        else served_row
    fin = share = samp = quant = 0.0
    rng = np.random.default_rng([int(driver.seed), 4])
    for seed, _s, _e, served, samples, q in driver.checked_calls(calls):
        want = sampler.draws(traffic["dists"], dkeys, driver.n, seed)
        have = (sampler.draws(traffic["dists"], dkeys, driver.n, seed, dt)
                if control else samples)
        for key, w in want.items():
            h = np.asarray(have[key], np.float64)
            samp = max(samp, float(np.max(np.abs(h - w) / np.abs(w))))
        for lv, v in q.items():
            quant = max(quant, _rel(v, np.quantile(served.makespans, lv)))
        pick = set(rng.choice(driver.n, size=min(per_call, driver.n),
                              replace=False).tolist())
        pick |= {int(np.argmax(served.makespans)),
                 int(np.argmin(served.makespans))}
        items = [({k: ("scale", float(want[k][i])) for k in want}, served, i)
                 for i in sorted(pick)]
        f, s = rows_gap(items, ref, got)
        fin, share = max(fin, f), max(share, s)
    return {"finish_rel": (fin, limits["finish_rel"]),
            "share_rel": (share, limits["share_rel"]),
            "sampler_rel": (samp, limits["sampler_rel"]),
            "quantile_rel": (quant, limits["quantile_rel"]),
            "lost": (float(len(driver.errors)
                           + (0 if driver.calls else 1)), 0.0),
            "off_path": (float(driver.off_path()), 0.0)}


def run(driver, config, traffic, control=False) -> dict:
    c = traffic["check"]
    if traffic["loop"] == "open":
        return whatif(driver, config, c["limits"], c["requests"], control)
    return mc(driver, config, traffic, c["limits"], c["calls"],
              c["draws_per_call"], control)


def passed(result: dict) -> bool:
    return all(np.isfinite(v) and v <= lim for v, lim in result.values())
