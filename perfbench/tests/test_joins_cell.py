"""The join configuration's own files (``tpch_sf10_joins``, PR 32): the
generator's three tables are consistent with one another and ``lineitem``
is ``tpch_lineitem``'s row for row; a rehearsal of the cell is ``correct``
and reads the join metrics; planted faults make a whole run ``correct:
false`` through ``run.main`` (one revenue off by 1e-6, the 11th group in
the 10th's place, the segment predicate dropped, the query sent to the
host engine); the float32 control fails through ``judge``; and a program
that places a string equality on the host ends in set-up with an exit code
other than 0 (``sources/memory_device_strings.py``)."""
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

import datagen
import run
from control_probe import control_reading

CELL = "tpch_sf10_joins.q3_resident"
ARGS = ["--workload", CELL, "--seed", "2400000556", "--seconds", "1",
        "--rehearsal-rows", "200000"]


@pytest.fixture(autouse=True)
def _rehearse_the_cells_program(monkeypatch):
    """A rehearsal's own configuration: below 4,194,304 rows the fused
    one-device fragment would take Q3 whole (and re-compile a fragment
    layer a run while it learns its bounds), where the cell, at 59,986,052
    rows and engine defaults, runs the operator pipeline. The cell's
    traffic file does not pin this; the rehearsals do."""
    real = run.engine_conf
    monkeypatch.setattr(run, "engine_conf", lambda cell: dict(
        real(cell), **{"spark.rapids.tpu.sql.fusedPipeline.enabled": False}))


def _config(name):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed,rows", [(7, 300_000), (2**31 + 7, 1_200_000),
                                       (2_400_000_777, 2_500_001)])
def test_the_three_tables_are_consistent(seed, rows):
    conf, flt = _config("tpch_sf10_joins"), _config("tpch_sf10")
    gen = datagen.load_module("generators", conf["generator"])
    tables = datagen.scaled_tables(conf, rows)
    plan = datagen.chunk_plan(conf, rows)
    li = pa.concat_tables([gen.generate("lineitem", tables, seed, i, n)
                           for i, n in plan])
    # lineitem is tpch_lineitem's, row for row
    base = datagen.load_module("generators", flt["generator"])
    same = pa.concat_tables([base.generate(
        "lineitem", datagen.scaled_tables(flt, rows), seed, i, n)
        for i, n in plan])
    assert li.equals(same) and li.num_rows == rows
    od = gen.generate("orders", tables, seed, 0, tables["orders"]["rows"])
    cu = gen.generate("customer", tables, seed, 0,
                      tables["customer"]["rows"])
    # orders: the distinct l_orderkey, in order, each once
    keys = li.column("l_orderkey").to_numpy()
    first = np.flatnonzero(np.diff(keys, prepend=keys[0] - 1))
    assert (od.column("o_orderkey").to_numpy() == keys[first]).all()
    assert abs(od.num_rows - rows / 4) < rows / 100
    # o_orderdate is the date the lines' ship dates were drawn from
    per_line = np.repeat(od.column("o_orderdate").to_numpy(),
                         np.diff(first, append=len(keys)))
    lag = (li.column("l_shipdate").to_numpy() - per_line).astype(int)
    assert lag.min() >= 1 and lag.max() <= 121
    assert set(od.column("o_shippriority").to_pylist()) == {0}
    # dbgen: no customer whose key is a multiple of 3 has an order
    ck = od.column("o_custkey").to_numpy()
    assert ck.min() >= 1 and ck.max() <= cu.num_rows and (ck % 3 != 0).all()
    assert len(np.unique(ck)) > min(od.num_rows, cu.num_rows * 2 // 3) * .6
    assert (cu.column("c_custkey").to_numpy()
            == np.arange(1, cu.num_rows + 1)).all()
    share = pc.value_counts(cu.column("c_mktsegment")).to_pylist()
    assert len(share) == 5 and all(
        abs(s["counts"] / cu.num_rows - 0.2) < 0.01 for s in share)
    # any subset of columns, and a second ask, come out the same
    part = gen.generate("orders", tables, seed, 0, 0,
                        ["o_orderdate", "o_orderkey"])
    assert part.equals(od.select(part.column_names))
    assert gen.generate("customer", tables, seed, 0, cu.num_rows,
                        ["c_mktsegment"]).equals(cu.select(["c_mktsegment"]))


@pytest.mark.parametrize("seed", [11, 2_400_000_777, 2**31 + 5])
def test_reference_passes_and_the_float32_control_fails(seed):
    cell = run.load_cell(CELL)
    tables = datagen.scaled_tables(cell["config"], 2_500_000)
    same = control_reading(cell, tables, seed, cell["config"]["precision"])
    assert all(c["value"] == 0 for c in same.values()), same
    low = control_reading(cell, tables, seed, "float32")
    assert low["revenue_rel_gap"]["value"] > low["revenue_rel_gap"]["limit"]
    assert low["shape_mismatch"]["value"] == 0 == low["count_gap"]["value"]
    ref = datagen.load_module("references", cell["check"]["reference"])
    want = datagen.reference_answer(
        cell["config"], tables, seed, run.query_columns(cell, tables),
        cell["check"]["reference"], cell["query"])
    assert len(want) == ref.CANDIDATES and ref.near_ties(want) == 0


def test_traced_rehearsal_is_correct_and_reads_the_join_metrics(capsys):
    assert run.main(ARGS + ["--trace", "1"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert {"join_rows_per_query", "join_build_ms",
            "conjuncts_above_joins"} <= set(line["counts"]["metrics_read"])
    assert "[require] conjuncts_above_joins=0.0 " in out
    assert "Cpu" not in out.split("[plan]")[1].split("[warm-up]")[0]


def _off_by_a_millionth(self, t):
    i = t.schema.get_field_index("revenue")
    rev = t.column(i).to_numpy().copy()
    rev[3] *= 1 + 1e-6
    return t.set_column(i, "revenue", pa.array(rev))


def _eleventh_for_tenth(self, t):
    text = run.load_cell(CELL)["text"]
    assert text.endswith("limit 10")
    more = _SOUND(self.session.sql(text[:-2] + "11"))
    return pa.concat_tables([more.slice(0, 9), more.slice(10, 1)])


_SOUND = None


@pytest.mark.parametrize("fault,numbers", [
    (_off_by_a_millionth, ["revenue_rel_gap"]),
    (_eleventh_for_tenth, ["key_mismatch"])])
def test_planted_fault_in_the_answer_is_not_correct(fault, numbers, capsys,
                                                    monkeypatch):
    global _SOUND
    from spark_rapids_tpu.api.dataframe import DataFrame
    _SOUND = DataFrame.collect_arrow
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: fault(self, _SOUND(self)))
    assert run.main(ARGS + ["--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["failed"] == 0, line
    assert [n for n, c in line["compared"].items()
            if c["value"] > c["limit"]] == numbers


def test_a_dropped_predicate_is_not_correct(capsys, monkeypatch):
    sound = run.load_cell

    def without_segment(name):
        cell = sound(name)
        assert "c_mktsegment = 'BUILDING'\n    and " in cell["text"]
        cell["text"] = cell["text"].replace(
            "c_mktsegment = 'BUILDING'\n    and ", "c_mktsegment <> 'x' and ")
        return cell
    monkeypatch.setattr(run, "load_cell", without_segment)
    assert run.main(ARGS + ["--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["failed"] == 0, line
    assert line["compared"]["key_mismatch"]["value"] > 0


def test_query_on_the_host_engine_counts_as_failed(capsys, monkeypatch):
    real = run.open_session

    def host_after_set_up(*a, **kw):
        # past the source kind's question: the tables are registered, then
        # every query of the run is planned for the host engine
        s = real(*a, **kw)
        s.conf = s.conf.set("spark.rapids.tpu.sql.enabled", False)
        return s
    monkeypatch.setattr(run, "open_session", host_after_set_up)
    assert run.main(ARGS + ["--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["failed"] == line["attempted"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())


def test_a_session_on_the_host_engine_ends_in_set_up(monkeypatch):
    real = run.engine_conf
    monkeypatch.setattr(run, "engine_conf", lambda cell: dict(
        real(cell), **{"spark.rapids.tpu.sql.enabled": False}))
    with pytest.raises(SystemExit, match="string equality predicate"):
        run.main(ARGS + ["--trace", "0"])


def test_a_program_that_filters_strings_on_the_host_ends_in_set_up(
        monkeypatch):
    from spark_rapids_tpu.exprs import comparison
    # what the parent of PR 32 answers: no dictionary form for col = 'x'
    monkeypatch.setattr(comparison.EqualTo, "dict_form", None)
    with pytest.raises(SystemExit, match="CpuFilter"):
        run.main(ARGS + ["--trace", "0"])
