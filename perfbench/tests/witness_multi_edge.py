"""Witness of the program fault that keeps the Montage cells out of the
benchmark: a process fed by two or more upstream processes finishes, in
the batched engines (``backend="numpy"`` and the fused ``"jax"``), when its
FIRST input allows, ignoring the others; the scalar solver
(``backend="loop"``) and the benchmark's plain reference agree with each
other and wait for every input.

    JAX_PLATFORMS=cpu python3 perfbench/tests/witness_multi_edge.py

Prints, for a workflow of ``n`` sources (source ``i`` needs ``1 + i`` CPU
seconds) feeding one sink, and for ``montage_3x4``, the finish times each
path gives.
"""

import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent / "src"))

import deploy  # noqa: E402
import reference  # noqa: E402


def fan_in(n: int) -> dict:
    src = [{"name": f"s{i}", "total_progress": 10.0,
            "data": [{"name": "in", "kind": "stream", "input_bytes": 10.0,
                      "input": {"starts": [0.0], "values": [10.0],
                                "slopes": [0.0]}}],
            "resources": [{"name": "cpu", "kind": "stream", "amount": 1.0 + i,
                           "alloc": {"starts": [0.0], "rates": [1.0]}}]}
           for i in range(n)]
    sink = {"name": "c", "total_progress": 10.0,
            "data": [{"name": f"d{i}", "kind": "stream", "input_bytes": 10.0,
                      "from": f"s{i}"} for i in range(n)],
            "resources": [{"name": "cpu", "kind": "stream", "amount": 0.5,
                           "alloc": {"starts": [0.0], "rates": [1.0]}}]}
    return {"processes": src + [sink]}


def paths(cfg: dict, overrides: list, proc: str) -> dict:
    plan = deploy.build_workflow(cfg).compile()
    specs = [deploy.program_scenario(o, deploy.data_keys(cfg)) for o in overrides]
    out = {b: plan.sweep(plan.prepare(specs) if b == "jax" else specs,
                         backend=b).finish[proc].tolist()
           for b in ("loop", "numpy", "jax")}
    ref = reference.Reference(cfg)
    out["reference"] = [float(ref.solve(o)["finish"][proc]) for o in overrides]
    return out


def main() -> None:
    for n in (1, 2, 3):
        print(json.dumps({"fan_in": n, "sink finish": paths(
            fan_in(n), [{}, {"s0.cpu": ("scale", 2.0)}], "c")}))
    cfg = json.loads((HERE.parent / "configs" / "montage_3x4.json").read_text())
    got = paths(cfg, [{}], "mDiffFit_9")
    print(json.dumps({"montage_3x4": "mDiffFit_9 finish", **got}))
    gap = abs(np.array(got["jax"]) - np.array(got["reference"])).max()
    print(json.dumps({"montage_3x4 fused vs reference, seconds": gap}))


if __name__ == "__main__":
    main()
