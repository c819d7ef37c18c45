"""The control at a small size on the CPU: the plain reference put in
the program's place at int8, one step below the bfloat16 the
configurations state, reads above one of each cell's limits where the
program reads below all of them (``bench/control.py`` makes the same
readings on the chip at the cells' own sizes; the limits at this size
are in ``test_bench_faults``)."""
import pytest

from bench import control, harness

from test_bench_faults import CELLS, small_cell

SEEDS = (2 ** 36 + 1, 2 ** 36 + 2, 2 ** 36 + 3)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails_where_the_program_passes(monkeypatch, name):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    cell = small_cell(name)
    limits = {k: v["limit"] for k, v in cell.limits["numbers"].items()}
    for seed in SEEDS:
        r = control.readings(cell, seed, control=True)
        assert all(r[k] <= lim for k, lim in limits.items()), r
        assert any(r[f"control_{k}"] > lim for k, lim in limits.items()), r
