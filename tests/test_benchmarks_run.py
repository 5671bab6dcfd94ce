"""The figure/table reproduction command (``benchmarks/run.py``) runs
over every registered program: Table I's suite and the field programs."""
import pytest

from repro.core.cfa.programs import FIELD_PROGRAMS, PROGRAMS


def test_table1_suite_runs_every_program(capsys):
    from benchmarks.run import table1_suite

    table1_suite()
    rows = {}
    for line in capsys.readouterr().out.splitlines():
        name, _us, derived = line.split(",")
        rows[name.removeprefix("table1/")] = dict(
            kv.split("=") for kv in derived.split(";") if "=" in kv)
    assert set(rows) == set(PROGRAMS) | set(FIELD_PROGRAMS)
    for name, row in rows.items():
        # w | t on axis 0: facet_0 packs from the oracle.  The sweep and the
        # oracle sum the same taps in orders XLA may fuse differently, so
        # they agree to float32 rounding: a few 1e-6 where jacobi2d9p-gol's
        # doubled centre grows values to ~1e2 over 8 planes.  A misplaced
        # value errs by the order of the values themselves
        if "max_err" in row:
            assert float(row["max_err"]) < 1e-4, name
    assert "max_err" in rows["fdtd2d"]
