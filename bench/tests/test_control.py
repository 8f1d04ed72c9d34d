"""The controls come out as not correct through the check's own verdict:
the reference put in the program's place with a plain float32 energy
ledger (the next precision below the configuration's compensated float32)
at the cells' own size, and with a bfloat16 ledger on the cells cut to CPU
size. The float64 reference itself passes its own check."""
import numpy as np
import pytest

import control
import tiny
from cells import load_cell


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("name,seed", [("nasa_ipsc.grid", 11),
                                       ("nasa_ipsc.grid", 2**31 + 12),
                                       ("nasa_ipsc.grid4", 13)])
def test_float32_control_is_not_correct(name, seed):
    low = control.control(load_cell(name), seed, control._dtype("float32"))
    assert low["correct"] is False, low
    assert low["checks"]["schedule_mismatches"]["value"] == 0
    assert (low["checks"]["energy_rel_err"]["value"]
            > low["checks"]["energy_rel_err"]["limit"])


@pytest.mark.parametrize("name", ["nasa_ipsc.grid", "nasa_ipsc.grid4"])
def test_bfloat16_control_is_not_correct(tiny_bench, name):
    cell = load_cell(name, bench=tiny_bench)
    for seed in (11, 2**31 + 12, 13):
        low = control.control(cell, seed, control._dtype("bfloat16"))
        assert low["correct"] is False, low
        assert low["checks"]["schedule_mismatches"]["value"] == 0
        same = control.control(cell, seed, np.float64)
        assert same["correct"] is True, same
        assert same["checks"]["energy_rel_err"]["value"] == 0.0
