"""The decimal configuration's own files (``tpch_sf10_decimal``, PR 28):
the generator gives ``tpch_lineitem``'s cents row for row; the exact
reference passes against itself and the ``double`` control (the same
query in float64 doubles, put in the program's place) fails; a planted
fault of one unit of the last digit, or of one mistyped result column,
makes a whole run ``correct: false``; a traced rehearsal reads the decimal
metrics; and a program without Spark's decimal result types ends in
set-up with an exit code other than 0 (``sources/memory_decimal.py``)."""
import decimal
import json
import os

import numpy as np
import pyarrow as pa
import pytest

import datagen
import run
from control_probe import control_reading

CELL = "tpch_sf10_decimal.q1_decimal_resident"
MONEY = ["l_quantity", "l_extendedprice", "l_discount", "l_tax"]
ARGS = ["--workload", CELL, "--seed", "2400000556", "--seconds", "1",
        "--rehearsal-rows", "200000"]


def _config(name):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed,chunk,rows", [(7, 0, 300_000),
                                             (2**31 + 7, 3, 123_457),
                                             (2_400_000_777, 57, 1_048_576)])
def test_decimal_generator_gives_the_float_generators_cents(seed, chunk,
                                                            rows):
    dec_conf, flt_conf = _config("tpch_sf10_decimal"), _config("tpch_sf10")
    dec = datagen.load_module("generators", dec_conf["generator"])
    flt = datagen.load_module("generators", flt_conf["generator"])
    d = dec.generate("lineitem", dec_conf["tables"], seed, chunk, rows)
    f = flt.generate("lineitem", flt_conf["tables"], seed, chunk, rows)
    assert d.column_names == f.column_names and d.num_rows == rows
    for c in d.column_names:
        if c not in MONEY:
            assert d.column(c).equals(f.column(c)), c
            continue
        assert d.schema.field(c).type == pa.decimal128(15, 2)
        words = np.frombuffer(d.column(c).chunk(0).buffers()[1], np.int64)
        cents = np.rint(f.column(c).to_numpy() * 100).astype(np.int64)
        assert (words[0::2] == cents).all() and (words[1::2] == 0).all(), c
    # any subset of columns comes out as in the whole table
    part = dec.generate("lineitem", dec_conf["tables"], seed, chunk, rows,
                        ["l_shipdate", "l_tax"])
    assert part.equals(d.select(part.column_names))


#: rows at which a group's sum_charge (about 1.9e10 x its rows, at scale 6)
#: is past 2^53, so that a double cannot hold it to the last digit. At
#: ISSUE 28's 400,000 rows the sums stay under 2^53 and the control reads
#: 0 on some seeds (seed 11: 0; my run on the sandbox's CPU, PR 28)
CONTROL_ROWS = 2_500_000


@pytest.mark.parametrize("seed", [11, 2_400_000_777, 2**31 + 5])
def test_exact_reference_passes_and_the_double_control_fails(seed):
    cell = run.load_cell(CELL)
    tables = datagen.scaled_tables(cell["config"], CONTROL_ROWS)
    same = control_reading(cell, tables, seed, cell["config"]["precision"])
    assert all(c["value"] == 0 == c["limit"] for c in same.values()), same
    low = control_reading(cell, tables, seed, "double")
    # doubles cannot hold the sums: the types and the counts are right
    assert low["sum_unscaled_gap"]["value"] > 0, low
    assert low["type_mismatch"]["value"] == 0 == low["count_gap"]["value"]


def test_a_run_of_the_double_control_is_not_correct(capsys):
    args = ARGS[:-1] + [str(CONTROL_ROWS), "--trace", "0"]
    assert run.main(args + ["--control", "double"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["failed"] == 0, line
    assert line["compared"]["sum_unscaled_gap"]["value"] > 0


def _one_unit_off(t):
    """sum_charge of the first group off by one unit of its last digit."""
    i = t.schema.get_field_index("sum_charge")
    cells = t.column(i).to_pylist()
    cells[0] += decimal.Decimal(1).scaleb(-t.schema.field(i).type.scale)
    return t.set_column(i, t.schema.field(i),
                        pa.array(cells, t.schema.field(i).type))


def _mistyped(t):
    """sum_charge as decimal(38,4) where Spark says decimal(38,6)."""
    i = t.schema.get_field_index("sum_charge")
    return t.set_column(i, "sum_charge",
                        t.column(i).cast(pa.decimal128(38, 4), safe=False))


@pytest.mark.parametrize("fault,number", [(_one_unit_off, "sum_unscaled_gap"),
                                          (_mistyped, "type_mismatch")])
def test_planted_fault_is_not_correct(fault, number, capsys, monkeypatch):
    from spark_rapids_tpu.api.dataframe import DataFrame
    sound = DataFrame.collect_arrow
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: fault(sound(self)))
    assert run.main(ARGS + ["--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["failed"] == 0, line
    assert [n for n, c in line["compared"].items()
            if c["value"] > c["limit"]] == [number]


def test_traced_rehearsal_reads_the_decimal_metrics(capsys):
    assert run.main(ARGS + ["--trace", "1"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert {"decimal_overflow_rows", "decimal_finish_ms"} <= \
        set(line["counts"]["metrics_read"])
    assert "[require] decimal_overflow_rows=0 " in out
    assert all(c["value"] == 0 for c in line["compared"].values())


def test_a_program_without_spark_decimal_types_ends_in_set_up(monkeypatch):
    from spark_rapids_tpu.exprs import arithmetic, decimal_rules
    # what the parent of PR 28 answers: decimal(15,2) * decimal(15,2) typed
    # as the wider operand
    monkeypatch.setattr(arithmetic.Multiply, "decimal_type",
                        staticmethod(decimal_rules.wider_type))
    with pytest.raises(SystemExit, match="decimal.31, 4"):
        run.main(ARGS + ["--trace", "0"])
