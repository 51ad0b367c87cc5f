"""The control: the reference in float32 put in the program's place must
fail the check's limits while the program's own readings pass, on several
seeds, through ``control.py`` at a size the CPU runs in seconds.  The same
script gives the readings on the chip at each cell's own size.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests/test_control.py
"""

import pytest
from conftest import tiny

import control


@pytest.mark.parametrize("workload", ["paper_fig5.whatif_open",
                                      "paper_fig5.mc10k"])
def test_control_fails_where_the_program_passes(workload):
    rows = control.readings(workload, [11, 3_000_000_013, 17], 2.0,
                            require_tpu=False, patch=tiny)
    assert all(r["program_passes"] for r in rows)
    assert not any(r["control_passes"] for r in rows)
