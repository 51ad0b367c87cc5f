"""The reader of the fused engine's fetched bytes
(``metrics/fetch_mb_per_sweep.mc.py``) on a synthetic run, and its silence
over a program without the ``engine_fetch_bytes`` counter.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_fetch_reader.py
"""

from types import SimpleNamespace

import pytest

import run
import tracing


def make_run(stats0, stats1, trace=None, window=None):
    driver = SimpleNamespace(stats0=stats0, stats1=stats1, calls=[])
    return run.Run(driver, 0, trace, window)


def test_fetch_mb_per_sweep_is_the_counter_over_the_sweeps():
    read = run.load_reader("fetch_mb_per_sweep.mc")
    got = read(make_run({"sweeps": 10, "engine_fetch_bytes": 2_000_000},
                        {"sweeps": 40, "engine_fetch_bytes": 197_000_000}))
    assert got == pytest.approx(6.5)
    assert read(make_run({"sweeps": 5, "engine_fetch_bytes": 7},
                         {"sweeps": 5, "engine_fetch_bytes": 7})) is None


def test_silent_without_the_counter():
    """The parent program's ``ServiceStats`` has no ``engine_fetch_bytes``."""
    tr = tracing.Trace(modules=[(0, 5_000, "jit_run(1)")])
    old = make_run({"sweeps": 10}, {"sweeps": 40}, tr, (0, 10_000))
    assert run.load_reader("fetch_mb_per_sweep.mc")(old) is None
