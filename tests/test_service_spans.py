"""Trace spans of the served path: the ``bm.*`` ``TraceAnnotation``s that
name the host's time per layer (PERF.md, "Spans and counters").

A small Monte Carlo call (64 draws in two chunks of 32) and one coalesced
what-if query (three requests in one sweep) are served twice untraced, to
warm every shape, then once each under ``jax.profiler.trace``; the traces
are read back with ``ProfileData``.  Checked: the span names are exactly
the documented set, they nest by layer, one ``bm.sweep`` per counted
sweep, the spans of one Monte Carlo call carry its request id on both
threads, their number grows with the chunks and not with the draws, and
the profiler leaves every result bit-identical.  The served paths read no
progress; a curve query on a direct sweep's report, traced apart, fetches
it once per solve.
"""

from __future__ import annotations

import contextlib
import glob
import re
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.analysis import AnalysisService
from repro.configs.paper_workflow import (build_workflow, mc_spec,
                                          sweep_scenarios)

ROOT = Path(__file__).resolve().parent.parent
N_DRAWS, CHUNK = 64, 32
WHATIF = [sweep_scenarios([x, x + 0.05]) for x in (0.2, 0.4, 0.6)]


def documented_spans() -> set:
    """The span names of PERF.md's "Spans and counters" table."""
    text = (ROOT / "PERF.md").read_text()
    return set(re.findall(r"^\| `(bm\.[a-z._]+)` \|", text, re.M))


def documented_counters() -> set:
    """The ``ServiceStats`` counters of the same table: the names in the
    first column of its rows that are not spans."""
    text = (ROOT / "PERF.md").read_text()
    table = text.split("### Spans and counters", 1)[1].split("\n## ", 1)[0]
    cells = re.findall(r"^\| ((?:`[a-z_]+`(?:, )?)+) \|", table, re.M)
    return {n for c in cells for n in re.findall(r"`([a-z_]+)`", c)}


def serve_mc(plan):
    with AnalysisService(plan, backend="jax", max_batch=CHUNK) as svc:
        s0 = svc.snapshot()
        mc = svc.query_mc(mc_spec(), N_DRAWS, seed=7, timeout=600)
        return [mc.report], s0, svc.snapshot()


def serve_whatif(plan):
    svc = AnalysisService(plan, backend="jax", max_batch=CHUNK,
                          autostart=False)
    s0 = svc.snapshot()
    futs = [svc.submit(scs) for scs in WHATIF]   # queued: one fused sweep
    svc.start()
    reps = [f.result(timeout=600) for f in futs]
    svc.close()
    return reps, s0, svc.snapshot()


def spans(trace_dir: str) -> list:
    """``(name, start, end, thread, stats)`` of every ``bm.*`` host event;
    a thread is the index of its line on the host plane."""
    path = max(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("bm."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                (plane.name, k), dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def plan():
    return build_workflow(0.5).compile()


@pytest.fixture(scope="module")
def served(plan, tmp_path_factory):
    """``{kind: (untraced reports, traced reports, stats before, stats
    after, spans)}`` for ``mc`` and ``whatif``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = {}
    for kind, serve in (("mc", serve_mc), ("whatif", serve_whatif)):
        serve(plan)                    # compiles, then the proven-cap ratchet
        plain = serve(plan)[0]
        d = str(tmp_path_factory.mktemp(f"trace_{kind}"))
        with jax.profiler.trace(d, profiler_options=opts):
            reps, s0, s1 = serve(plan)
        out[kind] = (plain, reps, s0, s1, spans(d))
    return out


@pytest.fixture(scope="module")
def curve_spans(plan, tmp_path_factory):
    """Spans of two direct sweeps and three curve queries on each report,
    traced after an untraced warm-up."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    pack = plan.prepare([sc for scs in WHATIF for sc in scs])
    ts = np.linspace(0.0, 300.0, 5)
    for traced in (False, True):
        reps = [plan.sweep(pack, backend="jax") for _ in range(2)]
        d = str(tmp_path_factory.mktemp("trace_curve"))
        with (jax.profiler.trace(d, profiler_options=opts) if traced
              else contextlib.nullcontext()):
            for rep in reps:
                rep.sample_progress("task3", ts)
                rep.data_ceiling("task3", ts)
                rep.kernel_finish_times("task1")
    return spans(d)


def inside(child, parent) -> bool:
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


def test_span_names_are_the_documented_set(served, curve_spans):
    doc = documented_spans()
    assert len(doc) == 10, doc
    seen = {s[0] for kind in served for s in served[kind][4]}
    assert seen == doc - {"bm.engine.fetch_progress"}
    assert {s[0] for s in curve_spans} == {"bm.engine.fetch_progress"}


def test_progress_fetched_once_per_solve_on_the_reading_thread(curve_spans):
    """Two reports, three curve queries each: two fetches, on the thread
    that asked, outside any sweep."""
    assert len(curve_spans) == 2
    assert len({s[3] for s in curve_spans}) == 1


@pytest.mark.parametrize("child,parent", [
    ("bm.pack", "bm.sweep"), ("bm.engine.stage", "bm.sweep"),
    ("bm.engine.put", "bm.sweep"), ("bm.engine.call", "bm.sweep"),
    ("bm.engine.fetch", "bm.sweep"), ("bm.report", "bm.sweep")])
def test_spans_nest_by_layer(served, child, parent):
    for kind in served:
        sp = served[kind][4]
        kids = [s for s in sp if s[0] == child]
        assert kids, (kind, child)
        parents = [s for s in sp if s[0] == parent]
        assert all(any(inside(c, p) for p in parents) for c in kids)


def test_mc_pack_inside_the_sweep_of_its_chunk(served):
    """The draws are packed on the worker, one ``bm.pack`` inside each
    chunk's ``bm.sweep`` (whose ``rows`` is the chunk), never on the
    caller's thread."""
    sp = served["mc"][4]
    packs = [s for s in sp if s[0] == "bm.pack"]
    sweeps = [s for s in sp if s[0] == "bm.sweep"]
    assert len(packs) == len(sweeps) == -(-N_DRAWS // CHUNK)
    for pk in packs:
        parent, = [s for s in sweeps if inside(pk, s)]
        assert parent[4]["rows"] == CHUNK
    caller = next(s[3] for s in sp if s[0] == "bm.mc.sample")
    assert all(pk[3] != caller for pk in packs)


@pytest.mark.parametrize("kind", ["mc", "whatif"])
def test_one_sweep_span_per_counted_sweep(served, kind):
    _plain, _reps, s0, s1, sp = served[kind]
    sweeps = [s for s in sp if s[0] == "bm.sweep"]
    assert len(sweeps) == s1["sweeps"] - s0["sweeps"] > 0
    if kind == "whatif":
        assert [(s[4]["n_req"], s[4]["rows"]) for s in sweeps] == [(3, 6)]


def test_mc_spans_carry_one_request_id_on_both_threads(served):
    sp = served["mc"][4]
    tagged = [s for s in sp if "req" in s[4]]
    assert {s[0] for s in tagged} == {"bm.mc.sample", "bm.sweep",
                                      "bm.mc.report"}
    assert len({s[4]["req"] for s in tagged}) == 1
    caller = next(s[3] for s in sp if s[0] == "bm.mc.sample")
    worker = {s[3] for s in sp if s[0] == "bm.sweep"}
    assert worker and caller not in worker


def test_spans_per_call_grow_with_chunks_not_draws(served):
    sp = served["mc"][4]
    chunks = -(-N_DRAWS // CHUNK)
    assert sum(s[0] == "bm.sweep" for s in sp) == chunks
    assert len(sp) <= 10 * chunks


@pytest.mark.parametrize("kind", ["mc", "whatif"])
def test_profiler_leaves_results_bit_identical(served, kind):
    plain, traced = served[kind][0], served[kind][1]
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert set(a.backends) == {"jax"}
        np.testing.assert_array_equal(a.makespans, b.makespans)
        np.testing.assert_array_equal(a.share_seconds, b.share_seconds)
        for n in a.order:
            np.testing.assert_array_equal(a.finish[n], b.finish[n])


def test_documented_counters_are_served(served):
    doc = documented_counters()
    assert doc == {"queue_wait_s", "queue_waits", "mc_draws_direct",
                   "mc_draws_materialized", "engine_level_steps",
                   "engine_fetch_bytes", "engine_progress_fetches"}
    for kind in served:
        assert doc <= set(served[kind][3])
        s0, s1 = served[kind][2], served[kind][3]
        assert s1["engine_fetch_bytes"] > s0["engine_fetch_bytes"]
        assert s1["engine_progress_fetches"] == s0["engine_progress_fetches"]


def test_level_steps_sum_each_sweeps_level_loops(plan, served):
    """``engine_level_steps`` grows once per fused sweep by the sum of its
    levels' loop trip counts: not per process, not per row."""
    _plain, _reps, s0, s1, _sp = served["whatif"]
    eng = plan._jax_engine
    steps0 = eng.level_steps
    rep = plan.sweep(plan.prepare([sc for scs in WHATIF for sc in scs]),
                     backend="jax")
    want = sum(rep.proc_results[lv[0]].iterations for lv in plan.levels)
    assert len(plan.levels) == 3 and want > 3
    assert eng.level_steps - steps0 == want
    assert s1["engine_level_steps"] - s0["engine_level_steps"] == want
    _plain, _reps, s0, s1, _sp = served["mc"]
    sweeps = s1["sweeps"] - s0["sweeps"]
    assert sweeps == -(-N_DRAWS // CHUNK)
    assert (s1["engine_level_steps"] - s0["engine_level_steps"]
            >= sweeps * len(plan.levels))
