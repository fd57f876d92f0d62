"""The control, the plain reference computed one precision below the
configuration's (bfloat16 for float32) and put in the program's place,
fails the comparison's limits where the program passes them, and so does
each of the engine's planted faults (a frozen state, half of each
minibatch, an altered selection). On the chip ``bench/control.py`` reads
the same at the cells' own sizes."""

import pytest

import control
import harness


@pytest.mark.parametrize("cell", ["tiny_engine_cell", "tiny_service_cell"])
def test_control_fails_where_program_passes(cell, tiny_root):
    reg = harness.Registry(tiny_root)
    limits = reg.limits(reg.cell(cell)["config"])
    for seed in (1, 2):
        r = control.readings(reg, cell, seed, seconds=0.3)
        assert all(r["program"][k] <= v for k, v in limits.items()), r
        assert any(r["control"][k] > v for k, v in limits.items()), r
        for fault, gaps in r.get("faults", {}).items():
            assert any(gaps[k] > v for k, v in limits.items()), (fault, r)
