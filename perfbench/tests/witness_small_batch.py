"""Witness of the fault that keeps the what-if cell out of the benchmark:
on the TPU, a Fig. 7 link split served by the fused engine in a sweep of
one scenario gives task2's finish time (186.645 s) with a relative error
of 3.5e-9 to 2.8e-8 for some link fractions, where the float64 the
deployment states gives 1e-14.  The host numpy twin, the scalar solver and
the same program on the CPU agree with the benchmark's reference.

    python3 perfbench/tests/witness_small_batch.py            # on the chip
    JAX_PLATFORMS=cpu python3 perfbench/tests/witness_small_batch.py

For each link rate of dl1 it prints task2's finish through the service in
a sweep of 1 (the scenario alone), of 2 (the scenario twice) and of 4 (with
three other link splits), beside the numpy twin, the scalar solver and the
reference, and each one's relative gap to the reference.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent / "src"))

import deploy  # noqa: E402
import reference  # noqa: E402

#: dl1 link rates (bytes/s) whose one-scenario sweeps lost digits on the chip
RATES = [9975098.936218472, 11030888.41001384, 8307915.506475991,
         11088624.09355082, 11807091.045143263]
OTHERS = [3e6, 6e6, 9e6]


def split(cfg: dict, r1: float) -> dict:
    V, L = cfg["constants"]["V"], cfg["constants"]["L"]
    return {"dl1.link": ("set", [0.0], [r1]),
            "dl2.link": ("set", [0.0, V / r1], [L - r1, L])}


def main() -> None:
    import jax

    from repro.analysis import AnalysisService

    cfg = json.loads((HERE.parent / "configs" / "paper_fig5.json").read_text())
    dk = deploy.data_keys(cfg)
    ref = reference.Reference(cfg)
    svc = AnalysisService(deploy.build_workflow(cfg), backend="jax",
                          max_batch=256)
    plan = svc.compile(deploy.build_workflow(cfg))
    print("device:", jax.devices()[0].device_kind)
    for r1 in RATES:
        o = split(cfg, r1)
        want = float(ref.solve(o)["finish"]["task2"])
        spec = deploy.program_scenario(o, dk)
        batches = {"B=1": [spec], "B=2": [spec, spec],
                   "B=4": [spec] + [deploy.program_scenario(split(cfg, r), dk)
                                    for r in OTHERS]}
        got = {k: float(svc.query(b, timeout=600).finish["task2"][0])
               for k, b in batches.items()}
        got["numpy"] = float(plan.sweep([spec], backend="numpy").finish["task2"][0])
        got["loop"] = float(plan.sweep([spec], backend="loop").finish["task2"][0])
        print(f"dl1 rate {r1!r}: reference {want!r}")
        for k, v in got.items():
            print(f"  {k:6s} {v!r}  rel {abs(v - want) / want:.3g}")
    svc.close()


if __name__ == "__main__":
    main()
