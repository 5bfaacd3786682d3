"""Cost-based optimizer (ref CostBasedOptimizer.scala) and supported-ops
doc/CSV generation (ref TypeChecks.scala SupportedOpsDocs/SupportedOpsForTools)."""
import pytest

from harness import assert_tpu_and_cpu_equal, tpu_session
from data_gen import IntGen, gen_df
from spark_rapids_tpu.api import functions as F


def _q(s):
    df = s.create_dataframe(gen_df({"k": IntGen(lo=0, hi=9),
                                    "v": IntGen()}, n=256))
    return df.filter(F.col("v") > 0).group_by("k").agg(
        F.count_star().with_name("n"))


def test_cost_optimizer_reverts_when_device_expensive():
    s = tpu_session({
        "spark.rapids.tpu.sql.optimizer.enabled": True,
        "spark.rapids.tpu.sql.optimizer.tpu.exec.defaultRowCost": 100.0,
        "spark.rapids.tpu.sql.optimizer.transition.cost": 100.0,
    })
    tree = _q(s)._physical().tree_string()
    assert "Cpu" in tree, tree


def test_cost_optimizer_keeps_device_when_cheap():
    # floor 0 = directly-attached TPU: per-row device advantage decides
    s = tpu_session({
        "spark.rapids.tpu.sql.optimizer.enabled": True,
        "spark.rapids.tpu.sql.optimizer.device.queryFloorSeconds": 0.0,
    })
    tree = _q(s)._physical().tree_string()
    assert "CpuAggregate" not in tree and "CpuFilter" not in tree, tree


def test_cost_optimizer_floor_reverts_small_queries():
    """Default floor (not yet re-measured on the attached chip): a
    256-row query loses to the per-query dispatch+fetch floor and runs
    whole-plan on the host engine (the engine must pick the winning
    engine)."""
    s = tpu_session({"spark.rapids.tpu.sql.optimizer.enabled": True})
    tree = _q(s)._physical().tree_string()
    assert "Cpu" in tree, tree


def test_cost_optimizer_keeps_device_at_scale():
    """A query whose host estimate exceeds device + floor stays device:
    aggregate over enough estimated rows (host ~1.2e-7 s/row vs floor)."""
    import numpy as np
    import pyarrow as pa
    s = tpu_session({"spark.rapids.tpu.sql.optimizer.enabled": True})
    n = 4_000_000
    t = pa.table({"k": pa.array(np.arange(n, dtype=np.int64) % 7),
                  "v": pa.array(np.ones(n))})
    df = (s.create_dataframe(t).filter(F.col("v") > 0)
          .group_by("k").agg(F.sum(F.col("v")).with_name("sv")))
    tree = df._physical().tree_string()
    assert "CpuAggregate" not in tree, tree


def test_cost_optimizer_uses_measured_rows():
    from spark_rapids_tpu.plan.cost import (_RUNTIME_ROWS, estimate_rows,
                                            plan_signature,
                                            record_runtime_rows)
    import pyarrow as pa
    s = tpu_session()
    t = pa.table({"v": pa.array(list(range(100)))})
    df = s.create_dataframe(t).filter(F.col("v") > 1_000_000)
    sig = plan_signature(df.plan)
    assert estimate_rows(df.plan) == 50.0        # crude halving guess
    df.collect_arrow()                           # actual: 0 rows
    assert sig in _RUNTIME_ROWS
    assert estimate_rows(df.plan) == 0.0         # measured feedback wins


def test_cost_optimizer_results_still_correct():
    assert_tpu_and_cpu_equal(
        _q, conf={"spark.rapids.tpu.sql.optimizer.enabled": True,
                  "spark.rapids.tpu.sql.optimizer.tpu.exec.defaultRowCost": 100.0})


def test_supported_ops_doc_generation():
    from spark_rapids_tpu.tools import (generate_supported_ops_md,
                                        generate_operators_score_csv,
                                        generate_supported_exprs_csv)
    md = generate_supported_ops_md()
    assert "TpuHashJoinExec" in md and "Cast" in md
    assert md == generate_supported_ops_md(), "generation not deterministic"
    score = generate_operators_score_csv()
    assert "CPUOperator,Score" in score and "TpuSortExec" in score
    csv = generate_supported_exprs_csv()
    assert csv.count("\n") > 100, "expression inventory suspiciously small"


def test_expression_inventory_marks_host_only():
    from spark_rapids_tpu.tools import expression_inventory
    inv = {r["name"]: r for r in expression_inventory()}
    assert inv["Add"]["device"]
    # string functions run on host columns (honest fallback tagging)
    assert any(r["module"] == "string_fns" for r in inv.values())


def test_config_docs_cover_registry():
    from spark_rapids_tpu.config import all_entries, generate_docs
    docs = generate_docs()
    for e in all_entries():
        if not e.internal:
            assert e.key in docs, e.key


def test_api_validation_clean():
    """ref api_validation/ApiValidation.scala: the registries must conform
    to the exec/expression/aggregate interfaces with docs coverage."""
    from spark_rapids_tpu.tools.api_validation import validate_api
    assert validate_api() == []


def test_per_expression_disable_conf_falls_back_to_host():
    """ref GpuOverrides.scala:3935 — every ExprRule carries an enable conf;
    disabling it forces host evaluation with an explain reason, results
    unchanged."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.api import TpuSession, functions as F
    from spark_rapids_tpu.plan.meta import (fallback_counts,
                                            reset_fallback_counts)

    t = pa.table({"a": pa.array(np.arange(50, dtype=np.int64))})

    def run(sess):
        return (sess.create_dataframe(t)
                .select((F.col("a") * 3).alias("b"))
                .collect_arrow().column("b").to_pylist())

    base = run(TpuSession())
    reset_fallback_counts()
    off = run(TpuSession(
        {"spark.rapids.tpu.sql.expression.Multiply": "false"}))
    assert base == off
    assert any("Multiply disabled by" in k for k in fallback_counts())


def test_per_exec_disable_conf_converts_to_cpu():
    """ref GpuOverrides.scala:4121 per-ExecRule confs: a disabled exec
    converts to the CPU twin; differential results identical."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.api import TpuSession, functions as F

    t = pa.table({"a": pa.array(np.arange(50, dtype=np.int64)),
                  "g": pa.array((np.arange(50) % 4).astype(np.int64))})

    def run(sess):
        out = (sess.create_dataframe(t)
               .filter(F.col("a") > 5)
               .group_by("g")
               .agg(F.sum(F.col("a")).with_name("s"))
               .collect_arrow().to_pydict())
        return sorted(zip(out["g"], out["s"]))

    assert run(TpuSession()) == run(TpuSession(
        {"spark.rapids.tpu.sql.exec.Filter": "false",
         "spark.rapids.tpu.sql.exec.Aggregate": "false"}))


def test_op_confs_registered_and_documented():
    from spark_rapids_tpu.plan.op_confs import ensure_op_confs
    ensure_op_confs()
    from spark_rapids_tpu.config import _REGISTRY, generate_docs
    n_expr = sum(1 for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.sql.expression."))
    n_exec = sum(1 for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.sql.exec."))
    # breadth parity target: reference registers 239 confs total
    # (RapidsConf.scala); per-op enables are the long tail there too
    assert n_expr > 120, n_expr
    assert n_exec > 15, n_exec
    assert len(_REGISTRY) > 200
    docs = generate_docs()
    assert "spark.rapids.tpu.sql.expression.Multiply" in docs


def test_scale_test_harness():
    """ref integration_tests scaletest: parameterized scale run with
    host-oracle verification and a machine-readable report."""
    from spark_rapids_tpu.tools.scale_test import run_scale_test
    rep = run_scale_test(20_000, ["q6", "q1"], iters=1)
    assert rep["rows"] == 20_000
    assert rep["queries"]["q6"]["verified"]
    assert rep["queries"]["q1"]["output_rows"] > 0
    assert rep["queries"]["q1"]["placement"] in ("host", "device")


# ---------------------------------------------------------------------------
# Adaptive-stats persistence (r4: VERDICT #7 — measured walls/rows survive
# process exit so a cold process plans a seen shape correctly first try)
# ---------------------------------------------------------------------------

def test_stats_store_roundtrip(tmp_path, monkeypatch):
    import importlib
    monkeypatch.setenv("SRTPU_STATS_PATH", str(tmp_path / "stats.json"))
    monkeypatch.setenv("SRTPU_STATS_PERSIST", "1")
    from spark_rapids_tpu.plan import cost, stats_store
    importlib.reload(stats_store)
    cost.record_engine_wall("Agg[x](Scan[#abc#])", "device", 1.25)
    cost.record_engine_wall("Agg[x](Scan[#abc#])", "device", 0.75)
    cost.record_engine_wall("Agg[x](Scan[#123456#])", "host", 0.5)  # local
    cost.record_runtime_rows("Filter[c](Scan[#abc#])", 42)
    stats_store.mark_dirty()
    stats_store.save()
    walls, rows = {}, {}
    stats_store._loaded = False
    stats_store.load_into(walls, rows)
    assert walls[("Agg[x](Scan[#abc#])", "device")] == (2, 0.75)
    # process-local "#<id>#" signatures must never persist
    assert ("Agg[x](Scan[#123456#])", "host") not in walls
    assert rows["Filter[c](Scan[#abc#])"] == 42
    # live entries win over persisted ones on merge
    walls2 = {("Agg[x](Scan[#abc#])", "device"): (5, 0.1)}
    stats_store._loaded = False
    stats_store.load_into(walls2, {})
    assert walls2[("Agg[x](Scan[#abc#])", "device")] == (5, 0.1)


def test_content_fingerprint_stable_and_distinct():
    import pyarrow as pa
    from spark_rapids_tpu.plan.cost import _pin_table
    t1 = pa.table({"a": [1, 2, 3], "b": ["x", "y", "z"]})
    t2 = pa.table({"a": [1, 2, 3], "b": ["x", "y", "z"]})
    t3 = pa.table({"a": [1, 2, 4], "b": ["x", "y", "z"]})
    assert _pin_table(t1) == _pin_table(t1)          # memo stable
    assert _pin_table(t1) == _pin_table(t2)          # content-addressed
    assert _pin_table(t1) != _pin_table(t3)          # data-sensitive


def test_measured_walls_flip_host_to_device():
    """r4: arbitration is BIDIRECTIONAL — when the measured device wall
    beats the measured host wall, the per-node model reverts must not
    fire (before this, a shape the model mispriced onto a slow host twin
    stayed there forever, walls ignored)."""
    import pyarrow as pa
    from harness import tpu_session
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.plan import cost

    t = pa.table({"k": list(range(100)) * 10, "v": [1.0] * 1000})
    conf = {"spark.rapids.tpu.sql.optimizer.enabled": True}

    def physical():
        s = tpu_session(conf)
        df = (s.create_dataframe(t).group_by("k")
              .agg(F.sum(F.col("v")).with_name("s")))
        return df, df._physical().tree_string()

    df, _tree = physical()
    sig = cost.plan_signature(df.plan)
    # poison: host measured fast twice -> host wholesale
    cost._ENGINE_WALLS.clear()
    cost.record_engine_wall(sig, "host", 0.001)
    cost.record_engine_wall(sig, "host", 0.001)
    cost.record_engine_wall(sig, "device", 5.0)
    cost.record_engine_wall(sig, "device", 5.0)
    _df, tree_host = physical()
    assert "!" in tree_host, tree_host          # host chosen
    # now the device wall measures faster -> device wholesale
    cost._ENGINE_WALLS.clear()
    cost.record_engine_wall(sig, "host", 5.0)
    cost.record_engine_wall(sig, "host", 5.0)
    cost.record_engine_wall(sig, "device", 0.001)
    cost.record_engine_wall(sig, "device", 0.001)
    _df, tree_dev = physical()
    assert "CpuAggregate" not in tree_dev, tree_dev
    cost._ENGINE_WALLS.clear()
