"""Every process of the Montage deployment (``perfbench/configs/
montage_pegasus_3x4.json``: levels of 12 and 23 processes, joins of 2, 12,
13 and 23 inputs, burst edges) under 8 draws of its Monte Carlo traffic
(``montage_mc10k``): the fused engine against the scalar solver and the
benchmark's reference at 1e-9.  Its own file, so that under
``--dist loadfile`` another worker runs it beside ``test_fan_in.py``.
"""

from __future__ import annotations

import json

from test_fan_in import BENCH, assert_same, deploy, reference, sampler, \
    sweep, time_limit


def test_montage_every_process_fused_against_loop():
    cfg = json.loads((BENCH / "configs" / "montage_pegasus_3x4.json")
                     .read_text())
    traffic = json.loads((BENCH / "traffic" / "montage_mc10k.json")
                         .read_text())
    draws = sampler.draws(traffic["dists"], deploy.data_keys(cfg), 8,
                          3_000_000_017)
    ovs = [{k: ("scale", float(v[i])) for k, v in draws.items()}
           for i in range(8)]
    with time_limit(240):
        plan, _specs, got = sweep(cfg, ovs, "jax")
        _plan, _specs, loop = sweep(cfg, ovs, "loop")
    assert [len(lv) for lv in plan.levels] == [12, 23, 1, 1, 12, 1, 1, 1, 1]
    procs = [p["name"] for p in cfg["processes"]]
    assert len(procs) == 53
    assert_same(got, loop, procs)
    ref = reference.Reference(cfg)
    assert_same(got, [ref.solve(o) for o in ovs], procs)
    # mAdd waits for the image table as well as every tile
    assert (got.finish["mAdd"] > got.finish["mImgtbl"]).all()
