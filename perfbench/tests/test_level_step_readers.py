"""The readers of the fused engine's lockstep steps
(``metrics/level_steps_per_sweep.mc.py``, ``metrics/device_us_per_step.mc.py``)
on a synthetic run and on the small CPU trace (``data/cpu_window.xplane.pb``),
and their silence over a program without the ``engine_level_steps`` counter.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_level_step_readers.py
"""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

import run
import tracing

HERE = Path(__file__).resolve().parent
TRACE = HERE / "data" / "cpu_window.xplane.pb"


def reader(name: str):
    """The reader module itself, so a test may point its pattern at the
    CPU trace's operations."""
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", HERE.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_run(stats0, stats1, trace=None, window=None):
    driver = SimpleNamespace(stats0=stats0, stats1=stats1, calls=[])
    return run.Run(driver, 0, trace, window)


COUNTED = ({"sweeps": 10, "engine_level_steps": 1000},
           {"sweeps": 40, "engine_level_steps": 4300})


def test_steps_per_sweep_is_the_counter_over_the_sweeps():
    read = run.load_reader("level_steps_per_sweep.mc")
    assert read(make_run(*COUNTED)) == 3300 / 30
    assert read(make_run({"sweeps": 5, "engine_level_steps": 7},
                         {"sweeps": 5, "engine_level_steps": 7})) is None


@pytest.mark.parametrize("name", ["level_steps_per_sweep.mc",
                                  "device_us_per_step.mc"])
def test_silent_without_the_counter(name):
    """The parent program's ``ServiceStats`` has no ``engine_level_steps``."""
    tr = tracing.Trace(modules=[(0, 5_000, "jit_run(1)")])
    old = make_run({"sweeps": 10}, {"sweeps": 40}, tr, (0, 10_000))
    assert run.load_reader(name)(old) is None


def test_device_us_per_step_on_a_synthetic_trace():
    """Module time clipped to the window, in us, over the counted steps;
    modules of other programs do not count."""
    tr = tracing.Trace(modules=[(0, 2_000_000, "jit_run(7)"),
                                (3_000_000, 4_500_000, "jit_run"),
                                (1_000_000, 9_000_000, "jit_other"),
                                (9_500_000, 12_000_000, "jit_run(7)")])
    read = run.load_reader("device_us_per_step.mc")
    got = read(make_run(*COUNTED, tr, (1_000_000, 10_000_000)))
    assert got == pytest.approx((1_000_000 + 1_500_000 + 500_000) * 1e-3 / 3300)
    assert read(make_run(*COUNTED, tracing.Trace(), (0, 1))) is None
    assert read(make_run(*COUNTED)) is None            # untraced run


def test_device_us_per_step_on_the_recorded_cpu_trace():
    """On the CPU the executables' work is the PjRt client's operations; with
    the reader's pattern on the two matmuls of each call, the value is
    their time in the window over the steps."""
    tr = tracing.load(str(TRACE), classify=lambda plane, line: (
        "module" if line.startswith("tf_XLAPjRtCpuClient")
        else "host" if plane == "/host:CPU" else None))
    lo, hi = tracing.window(tr)
    mod = reader("device_us_per_step.mc")
    mod.PATTERN = r"^dot_general"
    ns, n = tracing.named_ns(tr.modules, mod.PATTERN, lo, hi)
    assert n == 12 and ns > 0
    got = mod.read(make_run(*COUNTED, tr, (lo, hi)))
    assert got == ns * 1e-3 / 3300
    assert reader("level_steps_per_sweep.mc").read(
        make_run(*COUNTED, tr, (lo, hi))) == 110.0
