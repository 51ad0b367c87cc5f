"""A finite upper reading of ``finish_rel`` and ``share_rel`` on the cell
``montage_3x4.mc10k``.

The reference computed in float32 breaks down on this deployment: mAdd's
progress runs to 7.9e8 (bytes of mosaic), where a float32 step is 64, so
its finish tests do not meet their tolerance, and most rows never end.  One
step below float64, this reading does not depend on it: the float64
reference solved on the factors drawn in float32 (``sampler.draws(...,
np.float32)``, the draws the control puts in the program's place), against
the float64 reference on the float64 draws, through the check's own
comparison (``check.rows_gap``).  It must fail the cell's limits.

    python3 perfbench/tests/test_montage_control.py 3140001501 3140001502

prints one JSON line per seed, on 66 rows drawn from the seed.
"""

import json
import sys

import numpy as np

import check
import deploy
import reference
import run
import sampler

WORKLOAD = "montage_3x4.mc10k"


def reading(seed: int, rows: int) -> dict:
    _b, _c, config, traffic = run.load_cell(WORKLOAD)
    dkeys = deploy.data_keys(config)
    n = int(traffic["draws"])
    want = sampler.draws(traffic["dists"], dkeys, n, seed)
    have = sampler.draws(traffic["dists"], dkeys, n, seed, np.float32)
    pick = np.random.default_rng([seed, 4]).choice(n, rows, replace=False)
    ref = reference.Reference(config)
    items = [({k: ("scale", float(want[k][i])) for k in want}, None, int(i))
             for i in sorted(pick)]

    def rounded(ov, _served, row):
        return ref.solve({k: ("scale", float(have[k][row])) for k in ov})

    fin, share = check.rows_gap(items, ref, rounded)
    lim = traffic["check"]["limits"]
    return {"seed": seed, "rows": rows, "finish_rel": fin, "share_rel": share,
            "fails": bool(fin > lim["finish_rel"] or share > lim["share_rel"])}


def test_float32_draws_fail_the_limits():
    for seed in (11, 3_000_000_013):
        r = reading(seed, 12)
        assert np.isfinite(r["finish_rel"]) and np.isfinite(r["share_rel"])
        assert r["finish_rel"] > 1e-9 and r["fails"]


if __name__ == "__main__":
    for s in sys.argv[1:]:
        print(json.dumps(reading(int(s), 66)), flush=True)
