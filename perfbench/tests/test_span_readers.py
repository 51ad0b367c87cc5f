"""The readers of the program's spans and queue counters (``spans.py`` and
six ``metrics/*.mc.py``) on a traced CPU run of ``paper_fig5.mc10k`` at a
tiny size.  One test, as a traced run owns ``perfbench/.trace/``.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_span_readers.py
"""

import contextlib
import io
import json
import math

from conftest import tiny

SPAN_METRICS = ["mc_sample_ms.mc", "engine_stage_ms.mc", "transfer_ms.mc",
                "report_ms.mc"]
NEW = SPAN_METRICS + ["pack_us_per_scenario.mc", "queue_wait_ms.mc"]


def test_readers_report_what_fits_inside_a_call(monkeypatch):
    import drivers
    import run

    seen = []

    class Kept(drivers.ClosedMC):
        def setup(self):
            seen.append(self)
            super().setup()

    monkeypatch.setitem(drivers.DRIVERS, "closed_mc", Kept)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", "paper_fig5.mc10k", "--seed",
                       "3000000017", "--seconds", "3", "--trace", "1"],
                      require_tpu=False, patch=tiny)
    assert rc == 0
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in NEW:
        assert name in m and math.isfinite(m[name]) and m[name] > 0, name

    driver = seen[0]
    per_call = sum(m[k] for k in SPAN_METRICS) \
        + m["pack_us_per_scenario.mc"] * 1e-3 * driver.n
    wall_ms = sum(e - s for _seed, s, e, *_ in driver.calls) * 1e3 \
        / len(driver.calls)
    assert per_call <= wall_ms
