"""Configuration and scenario files -> the system under test's public API.

The only module of the benchmark that builds program objects: a deployment
(``configs/<name>.json``) becomes a ``repro.core.Workflow``, a generated
scenario becomes a ``repro.analysis.scenarios`` override, and a traffic
file's distributions become a Monte Carlo spec.  The plain reference
(:mod:`reference`) reads the same files on its own.
"""

from __future__ import annotations

import numpy as np


def build_workflow(config: dict):
    from repro.core import DataDep, PPoly, Process, ResourceDep, Workflow

    wf = Workflow()
    for p in config["processes"]:
        total = float(p["total_progress"])
        data = {}
        for d in p["data"]:
            make = {"stream": DataDep.stream, "burst": DataDep.burst}[d["kind"]]
            data[d["name"]] = make(float(d["input_bytes"]), total)
        resources = {r["name"]: ResourceDep.stream(float(r["amount"]), total)
                     for r in p["resources"]}
        proc = Process(p["name"], data=data, resources=resources,
                       total_progress=total).identity_output()
        alloc = {r["name"]: PPoly.step(r["alloc"]["starts"], r["alloc"]["rates"])
                 for r in p["resources"]}
        wf.add(proc, resources=alloc, start_after=p.get("start_after") or None)
        for d in p["data"]:
            if not d.get("from"):
                wf.set_data_input(p["name"], d["name"], _pl(d["input"]))
    for p in config["processes"]:
        for d in p["data"]:
            if d.get("from"):
                wf.connect(d["from"], p["name"], d["name"])
    return wf


def _pl(fn: dict):
    from repro.core import PPoly

    return PPoly(np.asarray(fn["starts"], np.float64),
                 [[v, s] if s else [v]
                  for v, s in zip(fn["values"], fn["slopes"])])


def program_scenario(overrides: dict, data_keys: set, label: str = ""):
    """A generated scenario (``{"proc.input": ("set", starts, rates) |
    ("scale", x)}``) as the program's what-if override."""
    from repro.analysis import scenarios
    from repro.core import PPoly

    res, dat = {}, {}
    for key, ov in overrides.items():
        if key in data_keys:
            dat[key] = float(ov[1])
        elif ov[0] == "set":
            res[key] = PPoly.step(list(ov[1]), list(ov[2]))
        else:
            res[key] = float(ov[1])
    return scenarios.override(resources=res, data=dat, label=label)


def program_mc_spec(dists: dict, data_keys: set):
    """``{"proc.input": [family, *params]}`` as the program's Monte Carlo
    spec (``repro.analysis.dist``)."""
    from repro.analysis import dist, scenarios

    make = {"lognormal": lambda m, s: dist.lognormal(median=m, sigma=s),
            "uniform": dist.uniform, "triangular": dist.triangular}
    res, dat = {}, {}
    for key, (family, *params) in dists.items():
        (dat if key in data_keys else res)[key] = make[family](*params)
    return scenarios.override(resources=res, data=dat, label="mc")


def data_keys(config: dict) -> set:
    return {f"{p['name']}.{d['name']}" for p in config["processes"]
            for d in p["data"] if not d.get("from")}
