import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def tiny(t):
    """A traffic file cut to a size the CPU runs in seconds (tests only)."""
    t.setdefault("service", {})["max_batch"] = 4
    if t["loop"] == "open":
        t["rate_per_s"] = 20.0
        t["request_size"]["max"] = 4
        t["check"]["requests"] = 6
    else:
        t["draws"] = 300
        t["check"]["draws_per_call"] = 16


@pytest.fixture
def run_cell(capsys):
    """Drive ``run.main`` past its look for a chip, at a tiny size; returns
    the parsed result line."""
    import run

    def go(workload, seed=3_000_000_017, seconds=3):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      require_tpu=False, patch=tiny)
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go
