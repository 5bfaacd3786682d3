"""The Q18 configuration's own files (``tpch_sf10_groups``, PR 35): the
generator's tables are ``tpch_joins``'s with ``o_totalprice`` and
``c_name`` beside them and ``lineitem`` is ``tpch_lineitem``'s row for
row; a rehearsal of the cell is ``correct``, runs every operator on the
device and reads the aggregate's and the joins' metrics; planted faults
make a whole run ``correct: false`` through ``run.main`` (one
``o_totalprice`` off by a cent, one order under the HAVING let through,
the query sent to the host engine); the float32 control fails through
``judge``."""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

import datagen
import run
from control_probe import control_reading

CELL = "tpch_sf10_groups.q18_resident"
#: 2,500,000 lines are about 625,000 orders, some 30 of which pass the
#: HAVING: an answer with rows in it, under the LIMIT
ARGS = ["--workload", CELL, "--seed", "2400000556", "--seconds", "1",
        "--rehearsal-rows", "2500000"]


@pytest.fixture(autouse=True)
def _rehearse_the_cells_program(monkeypatch):
    """A rehearsal's own configuration, as ``test_joins_cell.py``'s: below
    4,194,304 rows the fused one-device fragment would take the query
    whole, where the cell, at 59,986,052 rows and engine defaults, runs
    the operator pipeline; and 262,144-row batches, so that the aggregate
    on ``l_orderkey`` has ten partials that pass its cap together and
    finishes in partitions, as the cell's 58 do."""
    real = run.engine_conf
    monkeypatch.setattr(run, "engine_conf", lambda cell: dict(
        real(cell), **{"spark.rapids.tpu.sql.fusedPipeline.enabled": False,
                       "spark.rapids.tpu.sql.batchSizeRows": 262144}))
    sound = run.load_cell

    def with_small_chunks(name):
        cell = sound(name)
        cell["config"]["tables"]["lineitem"]["partition_rows"] = 262144
        return cell
    monkeypatch.setattr(run, "load_cell", with_small_chunks)


def _config(name):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed,rows", [(7, 300_000), (2**31 + 7, 1_200_000),
                                       (2_400_000_777, 2_500_001)])
def test_the_tables_are_tpch_joins_with_two_columns_more(seed, rows):
    conf, joins = _config("tpch_sf10_groups"), _config("tpch_sf10_joins")
    gen = datagen.load_module("generators", conf["generator"])
    tables = datagen.scaled_tables(conf, rows)
    plan = datagen.chunk_plan(conf, rows)
    li = pa.concat_tables([gen.generate("lineitem", tables, seed, i, n)
                           for i, n in plan])
    # lineitem is tpch_lineitem's, row for row
    flat = _config("tpch_sf10")
    base = datagen.load_module("generators", flat["generator"])
    same = pa.concat_tables([base.generate(
        "lineitem", datagen.scaled_tables(flat, rows), seed, i, n)
        for i, n in plan])
    assert li.equals(same) and li.num_rows == rows
    od = gen.generate("orders", tables, seed, 0, tables["orders"]["rows"])
    # orders: tpch_joins' columns as tpch_joins makes them
    theirs = datagen.load_module("generators", joins["generator"]).generate(
        "orders", datagen.scaled_tables(joins, rows), seed, 0, 0,
        ["o_orderkey", "o_custkey", "o_orderdate"])
    assert od.select(theirs.column_names).equals(theirs)
    # o_totalprice: the sum over the order's lines, to the cent
    lp = li.select(["l_orderkey", "l_extendedprice", "l_tax",
                    "l_discount"]).to_pandas()
    charge = lp.l_extendedprice * (1 + lp.l_tax) * (1 - lp.l_discount)
    want = charge.groupby(lp.l_orderkey).sum()
    total = od.column("o_totalprice").to_numpy()
    assert (od.column("o_orderkey").to_numpy() == want.index).all()
    assert np.abs(total - want.to_numpy()).max() < 0.0051
    assert np.abs(total * 100 - np.round(total * 100)).max() < 1e-6
    # c_name: 'Customer#' and the key in nine digits
    cu = gen.generate("customer", tables, seed, 0,
                      tables["customer"]["rows"])
    keys = cu.column("c_custkey").to_numpy()
    assert (keys == np.arange(1, cu.num_rows + 1)).all()
    at = [0, 1, 99_998, cu.num_rows - 1]
    assert cu.column("c_name").take(pa.array(at)).to_pylist() == [
        f"Customer#{keys[i]:09d}" for i in at]
    assert cu.column("c_name").null_count == 0
    # any subset of columns, and a second ask, come out the same
    part = gen.generate("orders", tables, seed, 0, 0,
                        ["o_totalprice", "o_orderkey"])
    assert part.equals(od.select(part.column_names))


def test_orders_past_the_having_at_full_size():
    """About 1 order in 23,000 holds seven lines whose quantities pass
    300 (1..7 lines an order, quantity uniform 1..50): 300..1,200 of the
    15.0M (622 to 685 over seven seeds), so LIMIT 100 cuts a real answer. dbgen's own data gives 57 at
    SF1, about ten times that at SF10. Counted on eight chunks (an eighth
    of the table) and held to an eighth of the range."""
    conf = _config("tpch_sf10_groups")
    base = datagen.load_module("generators", "tpch_lineitem")
    passed = 0
    for chunk in range(8):
        made = base._Chunk(conf["tables"], 2_400_000_556, chunk, 1_048_576)
        passed += int((np.bincount(made.order, weights=made.quantity)
                       > 300).sum())
    assert 300 * 8 / 58 <= passed <= 1200 * 8 / 58, passed


@pytest.mark.parametrize("seed", [11, 2_400_000_777, 2**31 + 5])
def test_reference_passes_and_the_float32_control_fails(seed):
    cell = run.load_cell(CELL)
    tables = datagen.scaled_tables(cell["config"], 3_000_000)
    same = control_reading(cell, tables, seed, cell["config"]["precision"])
    assert all(c["value"] == 0 for c in same.values()), same
    low = control_reading(cell, tables, seed, "float32")
    assert low["totalprice_cents_mismatch"]["value"] > 0
    assert low["totalprice_rel_gap"]["value"] > 1e-9
    assert low["shape_mismatch"]["value"] == 0 == low["count_gap"]["value"]
    assert low["quantity_gap"]["value"] == 0


def test_traced_rehearsal_is_correct_and_reads_the_new_metrics(capsys):
    assert run.main(ARGS + ["--trace", "1"]) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert {"agg_groups_per_query", "agg_merge_ms", "join_rows_per_query",
            "join_build_ms", "conjuncts_above_joins"} \
        <= set(line["counts"]["metrics_read"])
    assert "[require] conjuncts_above_joins=0.0 " in out
    plan = out.split("[plan]")[1].split("[warm-up]")[0]
    assert "Cpu" not in plan and "!" not in plan, plan
    # the semi-join directly above orders' scan, below both joins; the
    # top 100 selected on the device
    lines = [ln.strip() for ln in plan.splitlines()]
    semi = next(i for i, ln in enumerate(lines)
                if ln.startswith("* HashJoin[leftsemi"))
    assert lines[semi + 1].startswith("* InMemoryScan"), plan
    assert sum("Join[inner" in ln for ln in lines[:semi]) == 2, plan
    assert "; first 100]" in plan


def _a_cent_off(self, t):
    i = t.schema.get_field_index("o_totalprice")
    price = t.column(i).to_numpy().copy()
    price[3] += 0.01
    return t.set_column(i, "o_totalprice", pa.array(price))


def _an_order_under_the_having(self, t):
    text = run.load_cell(CELL)["text"]
    assert "> 300)" in text
    loose = _SOUND(self.session.sql(text.replace("> 300)", "> 290)")))
    return loose


_SOUND = None


@pytest.mark.parametrize("fault,numbers", [
    (_a_cent_off, ["totalprice_cents_mismatch", "totalprice_rel_gap"]),
    (_an_order_under_the_having, ["count_gap", "key_mismatch"])])
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


def test_query_on_the_host_engine_counts_as_failed(capsys, monkeypatch):
    real = run.open_session

    def host_after_set_up(*a, **kw):
        s = real(*a, **kw)
        s.conf = s.conf.set("spark.rapids.tpu.sql.enabled", False)
        return s
    monkeypatch.setattr(run, "open_session", host_after_set_up)
    assert run.main(ARGS + ["--trace", "0"]) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["failed"] == line["attempted"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())


def test_a_program_without_in_subqueries_ends_before_its_first_query(
        monkeypatch):
    """What the parent of PR 35 does with Q18's text: ``SqlError`` at the
    first ``session.sql``, after the tables are made, no query run."""
    from spark_rapids_tpu.sql import lowering
    from spark_rapids_tpu.sql.parser import SqlError

    def refuse(self, ast):
        raise SqlError("unexpected token 'select' at 134")
    monkeypatch.setattr(lowering._Lowerer, "_in_subquery_keys", refuse)
    with pytest.raises(SqlError):
        run.main(ARGS + ["--trace", "0"])
