#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest mean rate at which the
backlog does not grow over the window.

    python3 perfbench/knee.py --workload paper_fig5.whatif_open \\
        --seed 7 --seconds 20 --rates 50,100,200,400

One process, one set-up, then one window per rate (same traffic file,
``rate_per_s`` replaced).  For each rate it prints p50/p95 latency, the
requests still open at the window's close, and the ratio of the median
latency of the last quarter of requests (by due time) to the first
quarter; a backlog that grows shows as requests open at the close and a
ratio well above 1.  Needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import drivers  # noqa: E402
import run  # noqa: E402


def main(argv=None, *, require_tpu: bool = True, patch=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    _b, _c, config, traffic = run.load_cell(args.workload, patch)

    import jax

    if require_tpu and jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 3
    run.use_cache()
    t = time.time()
    d = drivers.OpenLoop(config, traffic, args.seed, args.seconds,
                         jax.profiler.TraceAnnotation)
    d.setup()
    print(f"knee: set-up {time.time() - t:.1f}s", file=sys.stderr, flush=True)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        d.traffic = dict(traffic, rate_per_s=rate)
        d.schedule(args.seed + i)
        d.window()
        due = d.t0 + np.array([r[0] for r in d.requests])
        lat = (d.done - due) * 1e3
        q = len(lat) // 4
        ok = np.isfinite(lat)
        row = {"rate_per_s": rate, "requests": len(lat),
               "scenarios": int(sum(len(r[1]) for r in d.requests)),
               "lost": int((~ok).sum()),
               "open_at_close": int(np.sum(~(d.done <= d.t0 + args.seconds))),
               "p50_ms": float(np.percentile(lat[ok], 50)),
               "p95_ms": float(np.percentile(lat[ok], 95)),
               "last_vs_first_quarter": float(np.nanmedian(lat[-q:])
                                              / np.nanmedian(lat[:q])),
               "sweeps": d.stats1["sweeps"] - d.stats0["sweeps"],
               "lag_p95_ms": float(np.nanpercentile(d.lags_ms(), 95))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    d.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
