"""Batched what-if scenario sweeps — the paper's headline use case at scale.

BottleMod's selling point (Sect. 6/8) is that re-analysis is cheap enough to
*try candidate allocations* online; Fig. 7 sweeps 600 link prioritizations.
This package holds the lockstep engines (:mod:`.engine`, :mod:`.jax_engine`),
the batched piecewise-linear algebra (:mod:`.plin`) and the scenario packing
(:mod:`.batch`) that power those sweeps.  It imports nothing from
:mod:`repro.analysis`; the front door is the compiled plan there
(compile-once / query-many)::

    plan = workflow.compile()        # topo, validation, packing: ONCE
    res = plan.sweep(scenarios)      # ...then sweep as often as you like
    res.top_k(5); res.bottleneck_report(res.best())

Backends (``plan.sweep(..., backend=...)``):

* ``"jax"`` — the fused engine of :mod:`.jax_engine`: the same lockstep
  event loop as ``lax.while_loop`` over stacked state, the whole workflow
  (solves + ceiling compositions) in ONE jitted XLA call; float64.  With a
  prepared :class:`~repro.analysis.pack.ScenarioPack` a re-sweep is a
  single compiled dispatch.
* ``"numpy"`` (alias ``"batched"``) — the lockstep engine of :mod:`.engine`:
  all scenarios advance one Algorithm-2 event per vectorized numpy
  iteration; the reference backend the jax engine must agree with, and the
  path that needs no compile for one-off lists.  Both batched engines serve
  the piecewise-QUADRATIC class: data inputs of degree <= 2 and
  non-negative piecewise-LINEAR resource rates (ramps — linear rate x
  linear requirement gives quadratic progress pieces, solved in closed
  form).
* ``"loop"`` — the scalar :func:`repro.core.solver.solve` per scenario; the
  reference the batched engines must agree with to float tolerance.
* ``"auto"`` (default) — the fast path (jax for packs, numpy for lists) for
  every scenario inside the batched function class, scalar loop for the
  rest; the routing is recorded per-scenario in ``Report.backends``,
  summarized in a single warning and by ``Report.summary()``.
"""

from __future__ import annotations

from .batch import Scenario, ScenarioBatch
from .engine import BatchProcResult, solve_batch
from .plin import BPL, UnsupportedScenario, compose_scalar

__all__ = [
    "Scenario", "ScenarioBatch", "BatchProcResult", "BPL",
    "UnsupportedScenario", "solve_batch", "compose_scalar",
]
