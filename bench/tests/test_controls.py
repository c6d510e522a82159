"""The controls, at a size a test run holds: the reference in fp8 reads
well above the program, so the comparison can tell them apart. The readings
at the cells' own sizes, which set the limits, come from
``bench/controls.py`` on the chip (PERF.md)."""

import pytest

from bench import controls


@pytest.mark.parametrize("name", ["olmo-1b.serve.batch", "forge.l2-gemm"])
def test_control_reads_above_the_program(tiny, name):
    rs = controls.readings(name, [4, 2**40 + 5], 1.0)
    for r in rs:
        assert r["control"] > 3 * r["program"]
        assert r["control"] > 0
