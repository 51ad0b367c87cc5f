"""The trace reduction (``tracing.py``) against a small trace recorded with
JAX on the CPU (``data/cpu_window.xplane.pb``, made by
``record_trace.py``).  On the CPU the "device" operations are the events of
the PjRt CPU client's threads; the host spans are the benchmark-style
``TraceAnnotation``s on the Python thread.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_tracing.py
"""

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import tracing  # noqa: E402

TRACE = HERE / "data" / "cpu_window.xplane.pb"


def cpu_lines(plane, line):
    if line.startswith("tf_XLAPjRtCpuClient"):
        return "op"
    return "host" if plane == "/host:CPU" else None


@pytest.fixture(scope="module")
def tr():
    return tracing.load(str(TRACE), classify=cpu_lines)


def test_union_merges_clips_and_drops_empty():
    ivs = [(0, 10, "a"), (5, 20, "b"), (30, 30, "z"), (25, 40, "c"),
           (50, 60, "d")]
    assert tracing.union(ivs, 2, 55) == [(2, 20), (25, 40), (50, 55)]


def test_busy_is_averaged_over_devices():
    tr = tracing.Trace(ops=[(0, 10, "a", 0), (5, 20, "b", 0), (0, 40, "c", 1)],
                       devices=2)
    assert tracing.busy_ns(tr, 0, 100) == (20 + 40) / 2
    assert tracing.gaps(tr, 0, 100) == [(40, 100)]


def test_busy_and_idle_match_a_brute_force_grid(tr):
    lo, hi = tracing.window(tr)
    grid = np.zeros((hi - lo) // 1000 + 1, bool)          # 1 us cells
    ops = [(s, e) for s, e, *_ in tr.ops if e > s]
    for s, e, *_ in ops:
        a, b = (max(s, lo) - lo) // 1000, (min(e, hi) - lo + 999) // 1000
        grid[a:b] = True
    busy = tracing.busy_ns(tr, lo, hi)
    assert ops and 0 < busy < hi - lo
    assert abs(busy - grid.sum() * 1000) <= 2000 * len(ops)
    gaps = tracing.gaps(tr, lo, hi)
    assert tr.devices == 1
    assert sum(e - s for s, e in gaps) == (hi - lo) - busy


def test_events_found_by_name(tr):
    lo, hi = tracing.window(tr)
    ns, n = tracing.named_ns(tr.ops, r"^dot_general", lo, hi)
    want = [(s, e) for s, e, name, _d in tr.ops if name.startswith("dot_general")
            and lo < e and s < hi]
    assert n == len(want) == 12             # two matmuls per call, six calls
    assert ns == sum(min(e, hi) - max(s, lo) for s, e in want)
    assert tracing.top_ops(tr, lo, hi)[0][1] > 0


def test_idle_gaps_attributed_to_the_host_span_in_them(tr):
    lo, hi = tracing.window(tr)
    top = tracing.idle_gaps(tr, lo, hi, k=6)
    # the innermost span: the Python tracer names the sleep inside bench.wait
    assert [name for name, _s in top] == ["python: $time sleep"] * 6
    secs = [s for _n, s in top]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] >= 0.06 and secs[-1] >= 0.01
