"""The control comes out not correct: the reference with every product in
float8 (e4m3), the precision below the configurations' bfloat16, put in
the program's place, against the float32 reference, on the CPU at the
sizes of ``tiny`` and with each cell's own limits."""
import pytest

from bench import check, spec
from bench.tests import tiny

CELLS = spec.cell_names(1)


@pytest.mark.parametrize("name", CELLS)
def test_float8_control_fails_a_limit(name):
    c = tiny.cell(name)
    limits = {k: c.limits[k]["limit"] for k in check.NUMBERS}
    ref = check.Reference(c.model, c.workload, c.config["reference"])
    low = check.Reference(c.model, c.workload, c.config["reference"],
                          "float8_e4m3fn")
    for seed in (2 ** 31 + 5, 7):
        values = check.gaps(low.run(seed), ref.run(seed))
        assert not check.verdict(values, limits), values
