"""A run with the timed path broken underneath reports ``correct`` false.

Each test skips the harness's look for a chip (a rehearsal, asked for by
name, at a tiny size on the CPU) and drives the rest of a run through
``run.main`` with one fault planted in the ENGINE, where the answer is
produced. Of the faults a cell can have, two apply to a one-chip query
engine: an answer altered where it is produced, and half of the rows left
out (the scan drops every second row group / batch). A training step that
returns its state unchanged and a left-out exchange between chips do not
exist in these cells. A third test sends the query to the host engine: a
right answer that did not come from the device counts as failed."""
import json
import os

import pyarrow as pa
import pytest

import run

# at this seed and size q3's answer holds two groups whose sum is NULL and
# one whose sum is 0.00, so a sound run is judged on those too
ARGS = ["--seed", "2400000556", "--seconds", "1", "--trace", "0",
        "--rehearsal-rows", "200000"]
with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    assert run.main(["--workload", cell] + ARGS) == 0
    line = last_line(capsys)
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["rehearsal"] is True and line["metrics"] == {}


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, capsys, monkeypatch):
    from spark_rapids_tpu.api.dataframe import DataFrame
    sound = DataFrame.collect_arrow

    def altered(self):
        t = sound(self)
        for i, f in enumerate(t.schema):
            if pa.types.is_floating(f.type):
                col = pa.array(t.column(i).to_numpy() * (1.0 + 1e-6))
                return t.set_column(i, f, col)
        raise AssertionError("no float column to alter")

    monkeypatch.setattr(DataFrame, "collect_arrow", altered)
    assert run.main(["--workload", cell] + ARGS) == 0
    line = last_line(capsys)
    assert line["correct"] is False, line
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_rows_left_out_is_not_correct(cell, capsys, monkeypatch):
    from spark_rapids_tpu.columnar import ColumnarBatch
    sound = ColumnarBatch.from_arrow          # a staticmethod
    config = run.load_cell(cell)["config"]
    fact_columns = set(config["tables"][config["fact"]]["columns"])

    def half(table, *a, **kw):
        # every batch of the fact table is built from half of its rows
        # (the dimensions stay whole)
        if set(table.column_names) <= fact_columns:
            table = table.slice(0, table.num_rows // 2)
        return sound(table, *a, **kw)

    monkeypatch.setattr(ColumnarBatch, "from_arrow", staticmethod(half))
    assert run.main(["--workload", cell] + ARGS) == 0
    line = last_line(capsys)
    assert line["correct"] is False, line


def test_query_on_the_host_engine_counts_as_failed(capsys, monkeypatch):
    real = run.engine_conf

    def host_conf(cell):
        return dict(real(cell), **{"spark.rapids.tpu.sql.enabled": False})

    monkeypatch.setattr(run, "engine_conf", host_conf)
    assert run.main(["--workload", CELLS[0]] + ARGS) == 0
    line = last_line(capsys)
    assert line["correct"] is False and line["failed"] == line["attempted"]
