"""Witness of a fault in the benchmark's plain reference (``reference.py``):
``solve_process`` can loop for ever where two ceiling lines cross.  After it
moves to a computed crossing ``x``, rounding may leave the faster line one
unit in the last place below the slower one; the next crossing it computes
is then ``x`` plus less than half an ulp of ``x``, which is ``x`` again.

    JAX_PLATFORMS=cpu python3 perfbench/tests/witness_reference_stall.py

Two sources feed one sink; source ``i``'s input arrives from
``0.1 (i + 1) + 0.013 i^2`` s at ``2 + 0.7 i`` bytes/s, and the sink's CPU
is three times the base.  With source 1's input 1.5 times faster the
reference stalls at t = 0.18297...; with 1.25 it does not.  The program's
engines take a crossing within 1e-9 s as passed, so they do not stall.
Prints one line per case, each solve under a 5 s alarm.
"""

import json
import signal
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import reference  # noqa: E402


def source(i: int) -> dict:
    d, r = 0.1 * (i + 1) + 0.013 * i * i, 2.0 + 0.7 * i
    return {"name": f"s{i}", "total_progress": 10.0,
            "data": [{"name": "in", "kind": "stream", "input_bytes": 10.0,
                      "input": {"starts": [0.0, d, d + 10.0 / r],
                                "values": [0.0, 0.0, 10.0],
                                "slopes": [0.0, r, 0.0]}}],
            "resources": [{"name": "cpu", "kind": "stream", "amount": 0.25,
                           "alloc": {"starts": [0.0], "rates": [1.0]}}]}


CONFIG = {"processes": [source(0), source(1), {
    "name": "c", "total_progress": 10.0,
    "data": [{"name": f"d{i}", "kind": "stream", "input_bytes": 10.0,
              "from": f"s{i}"} for i in range(2)],
    "resources": [{"name": "cpu", "kind": "stream", "amount": 1.5,
                   "alloc": {"starts": [0.0], "rates": [1.0]}}]}]}


def stalled(_sig, _frame):
    raise TimeoutError


def main() -> None:
    signal.signal(signal.SIGALRM, stalled)
    ref = reference.Reference(CONFIG)
    for speed in (1.25, 1.5):
        ov = {"c.cpu": ("scale", 3.0), "s1.in": ("scale", speed)}
        signal.alarm(5)
        try:
            out = {"sink finish": float(ref.solve(ov)["finish"]["c"])}
        except TimeoutError:
            out = {"sink finish": "stalled (no answer in 5 s)"}
        signal.alarm(0)
        print(json.dumps({"s1.in speed-up": speed, **out}))


if __name__ == "__main__":
    main()
