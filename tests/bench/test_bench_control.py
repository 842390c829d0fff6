"""The control: the plain reference computed in float32, put in the
program's place, must come out not correct against every cell's limits
(here at a size a test run holds; ``bench/calibrate.py`` reads it on the
chip at the cells' own sizes)."""
import pytest

from bench import harness

from test_bench_cells import TINY


@pytest.mark.parametrize("name", sorted(TINY))
def test_float32_reference_fails_the_check(name):
    spec = harness.load_cell(name)
    d = harness.make_driver(spec, 2 ** 31 + 23, **TINY[name])
    n_calls = 2
    for i in range(n_calls):
        d.call(i)
    got = d.check(n_calls, spec["traffic"]["checks"], control=True)
    assert any(got[k] > lim for k, lim in spec["limits"].items()), got
