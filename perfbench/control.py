#!/usr/bin/env python3
"""Readings that the ``check`` limits are set from.

    python3 perfbench/control.py --workload paper_fig5.whatif_open \\
        --seeds 11,12,13 --seconds 10

One process, one set-up, then for each seed one window at the cell's own
load and size, followed by the reference comparison twice: with what the
program served (the lower readings, which a sound run gives), and with the
reference in float32 put in the program's place (the control, whose
readings the limits must reject).  Prints one JSON line per seed.  The
benchmark's own runs never run this.  Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import drivers  # noqa: E402
import run  # noqa: E402


def readings(workload: str, seeds: list, seconds: float, *,
             require_tpu: bool = True, patch=None) -> list:
    _b, _c, config, traffic = run.load_cell(workload, patch)

    import jax

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("control: needs a TPU")
    run.use_cache()
    d = drivers.DRIVERS[traffic["loop"]](config, traffic, seeds[0], seconds,
                                         jax.profiler.TraceAnnotation)
    d.setup()
    out = []
    for seed in seeds:
        if traffic["loop"] == "open":
            d.schedule(seed)
        d.seed = seed
        d.window()
        prog = check.run(d, config, traffic)
        ctrl = check.run(d, config, traffic, control=True)
        row = {"seed": seed, "attempted": d.attempted(), "failed": d.failed(),
               "program": {k: v for k, (v, _l) in prog.items()},
               "control": {k: v for k, (v, _l) in ctrl.items()},
               "program_passes": check.passed(prog),
               "control_passes": check.passed(ctrl)}
        out.append(row)
        print(json.dumps(row), flush=True)
    d.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
