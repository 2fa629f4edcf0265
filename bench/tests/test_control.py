"""The lower-precision control comes out as not correct.

The control is the plain reference put in the program's place, computed
one step below the configuration's precision: float8 e4m3 inputs to every
linear layer of the bfloat16 LLM.  A run with
``--control`` compares the control's answers instead of the program's,
under the same limits, and must read ``correct: false``.  On the chip the
same was read at the cells' own sizes (``bench/readings.py``, PERF.md);
here it runs at the rehearsal's tiny size, with more checked requests so
that the sample holds as many compared positions as a chip run does.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_control.py -q
"""
from __future__ import annotations

import pytest

from test_faults import run_cell


@pytest.mark.parametrize("seed", [2147483811, 2147483812, 2147483813])
def test_llm_control_is_not_correct(seed, monkeypatch):
    from bench.harness import common

    find = common.find_cell

    def more_checked(name):
        manifest, cell, cfg, traffic = find(name)
        return manifest, cell, cfg, dict(
            traffic, sample={"requests": 24, "rows": 8})
    monkeypatch.setattr(common, "find_cell", more_checked)
    out = run_cell("qwen2.5-3b.offline", seed, control=True)
    assert out["correct"] is False, out["compared"]
