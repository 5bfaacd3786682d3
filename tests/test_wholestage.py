"""Whole-stage fusion + executable cache (ISSUE 6).

Fusion correctness battery: fused regions must be byte-identical to the
per-operator pipeline across the filter/project/agg/join/sort/window/
string suites, with and without injected OOM retries/splits mid-stage.
Cache-key tests cover digest/dtype/extra miss cases and corrupt
persistent entries; the disabled path must cost nothing (the
trace/metrics off-path contract).
"""
import os

import numpy as np
import pandas as pd
import pytest

from harness import tpu_session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.plan import exec_cache

FUSION_OFF = {"spark.rapids.tpu.fusion.enabled": False}


def _table(n=2000, seed=7):
    rng = np.random.RandomState(seed)
    return pd.DataFrame({
        "a": rng.randint(0, 100, n),
        "b": rng.uniform(-10, 10, n),
        "c": rng.randint(0, 5, n),
        "s": np.asarray([f"key-{i % 7:02d}" for i in range(n)],
                        dtype=object),
    })


def _chain(df):
    """A 3-op fusible region: filter -> project -> filter."""
    return (df.filter(F.col("a") > 10)
            .select((F.col("a") * 2).alias("a2"),
                    (F.col("b") + 1.5).alias("b1"),
                    F.col("c"), F.col("s"))
            .filter(F.col("a2") < 150))


QUERIES = {
    "plain": lambda df: _chain(df),
    "agg": lambda df: (_chain(df).group_by("c")
                       .agg(F.sum(F.col("b1")).with_name("sb"),
                            F.count_star().with_name("n"))
                       .order_by("c")),
    "sort": lambda df: _chain(df).order_by("a2", "c"),
    "strings": lambda df: (_chain(df).group_by("s")
                           .agg(F.max(F.col("a2")).with_name("m"))
                           .order_by("s")),
}


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_fused_matches_unfused(name):
    q = QUERIES[name]
    fused = q(tpu_session().create_dataframe(_table())).collect_arrow()
    plain = q(tpu_session(FUSION_OFF)
              .create_dataframe(_table())).collect_arrow()
    assert fused.equals(plain), f"{name}: fused result diverged"


def test_fused_join_and_window_match_unfused():
    left = _table(500, seed=1)
    right = pd.DataFrame({"c": np.arange(5), "w": np.arange(5) * 10.0})

    def q(s):
        df = _chain(s.create_dataframe(left))
        other = s.create_dataframe(right)
        j = df.join(other, on="c", how="inner")
        from spark_rapids_tpu.exprs import ColumnRef
        from spark_rapids_tpu.exprs.aggregates import Sum
        return j.with_window_column(
            "ws", Sum(ColumnRef("b1")), partition_by=["c"],
            order_by=[F.col("a2").asc()], frame=("rows", -1, 0))

    fused = q(tpu_session()).to_pandas()
    plain = q(tpu_session(FUSION_OFF)).to_pandas()
    key = ["c", "a2", "b1"]
    fused = fused.sort_values(key, kind="mergesort").reset_index(drop=True)
    plain = plain.sort_values(key, kind="mergesort").reset_index(drop=True)
    pd.testing.assert_frame_equal(fused, plain)


def test_fused_plan_is_visible_in_explain_and_trace():
    from spark_rapids_tpu.trace import Tracer, install_tracer
    s = tpu_session()
    q = _chain(s.create_dataframe(_table()))
    out = q.explain("physical")
    assert "WholeStage[fused=[" in out
    tr = Tracer()
    install_tracer(tr)
    try:
        q.collect_arrow()
        spans = [e for e in tr.snapshot()
                 if e.get("name") == "WholeStageExec"]
        assert spans, "no WholeStageExec span in the trace"
        assert spans[0]["args"].get("fused"), "span lost the fused=[...] arg"
    finally:
        install_tracer(None)


def test_explain_analyze_reports_per_op_rows_inside_fusion():
    s = tpu_session()
    out = _chain(s.create_dataframe(_table())).explain("analyze")
    assert "WholeStage[fused=[" in out
    # per-operator breakdown lines survive fusion, with exact rows
    assert "+ Filter[(a > 10)]" in out
    assert "+ Project[" in out
    for line in out.splitlines():
        if line.strip().startswith("+ "):
            assert "rows=" in line and "self=" in line


def test_fused_survives_injected_retry_oom():
    s = tpu_session()
    df = s.create_dataframe(_table(4096), num_partitions=4)
    q = (_chain(df).group_by("c")
         .agg(F.sum(F.col("b1")).with_name("sb")).order_by("c"))
    expect = (QUERIES["plain"](tpu_session(FUSION_OFF)
                               .create_dataframe(_table(4096)))
              .to_pandas().groupby("c")["b1"].sum())
    mm = s.exec_context().memory
    mm.force_retry_oom(1)
    try:
        got = q.to_pandas()
    finally:
        mm.clear_injections()
    np.testing.assert_allclose(
        got.set_index("c")["sb"].to_numpy(),
        expect.to_numpy(), rtol=1e-9)


def test_fused_survives_injected_split_mid_stage():
    """SplitAndRetryOOM mid-stage halves the input batch and re-runs the
    fused kernel over each piece: the concatenated pieces must be
    byte-identical to the unsplit run (the retry framework's idempotence
    contract applied to a fused region)."""
    import pyarrow as pa
    from spark_rapids_tpu.exec.wholestage import WholeStageExec
    from spark_rapids_tpu.mem import SpillableBatch, with_retry
    s = tpu_session()
    physical = _chain(s.create_dataframe(_table()))._physical()
    node = physical
    while not isinstance(node, WholeStageExec):
        node = node.children[0]
    ctx = s.exec_context()
    ref = pa.concat_tables(
        [node._run_fused(b.ensure_device())[0].to_arrow()
         for b in node.children[0].execute(ctx)])
    mm = ctx.memory
    splits = []

    def fn(sb):
        mm.reserve(8)                 # injected split fires here
        mm.release(8)
        splits.append(1)
        try:
            return node._run_fused(sb.get().ensure_device())[0].to_arrow()
        finally:
            sb.close()                # fn owns the consumed input

    pieces = [SpillableBatch(b.ensure_device(), mm)
              for b in node.children[0].execute(ctx)]
    mm.force_split_and_retry_oom(1)
    try:
        tabs = list(with_retry(pieces, fn, mm))
    finally:
        mm.clear_injections()
    assert len(tabs) > 1, "the injected split never fired"
    assert pa.concat_tables(tabs).equals(ref)


# ---------------------------------------------------------------------------
# executable cache
# ---------------------------------------------------------------------------

def test_warm_repeat_hits_cache_with_zero_compile():
    def run():
        s = tpu_session()
        return _chain(s.create_dataframe(_table())).collect_arrow()
    run()                                     # cold: builds the kernel
    st0 = exec_cache.stats()
    warm = run()                              # fresh session, same shape
    st1 = exec_cache.stats()
    assert st1["misses"] == st0["misses"], "warm repeat rebuilt a kernel"
    assert st1["hits"] > st0["hits"]
    assert st1["compile_s"] == st0["compile_s"], \
        "warm repeat paid XLA compile"
    assert warm.num_rows > 0


def test_cache_key_miss_cases():
    k1 = exec_cache.fused_key("digest-a", (("a", "bigint"),))
    k2 = exec_cache.fused_key("digest-b", (("a", "bigint"),))
    k3 = exec_cache.fused_key("digest-a", (("a", "double"),))
    k4 = exec_cache.fused_key("digest-a", (("a", "bigint"),), extra=(64,))
    assert len({k1, k2, k3, k4}) == 4
    # device kind is part of every key
    assert k1[2] == exec_cache.device_kind()
    # digest is stable and input-sensitive
    assert exec_cache.digest_of("x", "y") == exec_cache.digest_of("x", "y")
    assert exec_cache.digest_of("x", "y") != exec_cache.digest_of("xy")


def test_get_or_build_hit_and_miss_accounting():
    st0 = exec_cache.stats()
    key = exec_cache.fused_key("test-" + os.urandom(4).hex(), ())
    built = []

    def build():
        built.append(1)
        return lambda: 42
    fn1 = exec_cache.get_or_build(key, build)
    fn2 = exec_cache.get_or_build(key, build)
    assert fn1 is fn2 and len(built) == 1
    st1 = exec_cache.stats()
    assert st1["misses"] == st0["misses"] + 1
    assert st1["hits"] == st0["hits"] + 1


def test_corrupt_persistent_entry_falls_back_to_recompile(tmp_path):
    """Garbage in the persistent tier must never fail a query: entries
    jax cannot deserialize are recompiled, and the size trim tolerates
    unreadable files."""
    cache_dir = str(tmp_path / "xla_cache")
    os.makedirs(cache_dir)
    with open(os.path.join(cache_dir, "corrupt-entry"), "wb") as f:
        f.write(b"\x00not an executable\xff" * 64)
    s = tpu_session({"spark.rapids.tpu.compile.cache.dir": cache_dir})
    t = _chain(s.create_dataframe(_table())).collect_arrow()
    plain = _chain(tpu_session(FUSION_OFF)
                   .create_dataframe(_table())).collect_arrow()
    assert t.equals(plain)
    # trim walks the corrupt file without raising
    assert exec_cache.trim_persistent(cache_dir, 1) >= 1


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what a fresh process reports about the compile cache: the directory
#: after import, after a session conf tried to re-point it, and the
#: engine's own notion of the default and of the stats file
_CACHE_PROBE = """
import json, jax, spark_rapids_tpu as pkg
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.plan import exec_cache, stats_store
at_import = jax.config.jax_compilation_cache_dir
exec_cache.configure_from_conf(
    TpuConf({"spark.rapids.tpu.compile.cache.dir": "/conf/override"}))
print(json.dumps({"at_import": at_import,
                  "after_conf": jax.config.jax_compilation_cache_dir,
                  "default": pkg.DEFAULT_COMPILE_CACHE_DIR,
                  "stats": stats_store.store_path()}))
"""


def _probe_cache_dir(env_dir):
    """Run _CACHE_PROBE in a fresh CPU-pinned interpreter with (or,
    None, without) JAX_COMPILATION_CACHE_DIR."""
    import json
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "SRTPU_STATS_PATH")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["conf_override_not_sticky",
                                  "env_places_cache_repo_stands_down",
                                  "default_is_fixed_in_checkout"])
def test_compile_cache_dir_rule(case, tmp_path, monkeypatch):
    """Who places the persistent compile cache: the environment
    (JAX_COMPILATION_CACHE_DIR) when set — then no repo code sets a
    directory, not at import and not from compile.cache.dir — else ONE
    fixed directory inside the checkout, the same in every process,
    which a session conf may re-point and an empty conf restores."""
    if case == "conf_override_not_sticky":
        import jax
        from spark_rapids_tpu.config import TpuConf
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        exec_cache.configure_from_conf(TpuConf())   # settle on the default
        default = jax.config.jax_compilation_cache_dir or ""
        override = str(tmp_path / "session_cache")
        exec_cache.configure_from_conf(
            TpuConf({"spark.rapids.tpu.compile.cache.dir": override}))
        assert jax.config.jax_compilation_cache_dir == override
        exec_cache.configure_from_conf(TpuConf())
        assert (jax.config.jax_compilation_cache_dir or "") == default
    elif case == "env_places_cache_repo_stands_down":
        got = _probe_cache_dir("/x")
        assert got["at_import"] == "/x"
        assert got["after_conf"] == "/x"
        # the learned stats stay inside the checkout either way
        assert got["stats"].startswith(_REPO + os.sep)
    else:
        first, second = _probe_cache_dir(None), _probe_cache_dir(None)
        want = os.path.join(_REPO, ".srtpu_cache", "xla")
        assert first["at_import"] == first["default"] == want
        assert first["after_conf"] == "/conf/override"
        assert second == first
        assert first["stats"] == os.path.join(
            _REPO, ".srtpu_cache", "adaptive_stats.json")


def test_trim_persistent_evicts_oldest_first(tmp_path):
    d = str(tmp_path / "cache")
    os.makedirs(d)
    for i in range(4):
        with open(os.path.join(d, f"e{i}"), "wb") as f:
            f.write(b"x" * 100)
        os.utime(os.path.join(d, f"e{i}"), (i + 1, i + 1))
    removed = exec_cache.trim_persistent(d, 250)
    assert removed == 2
    assert sorted(os.listdir(d)) == ["e2", "e3"]
    assert exec_cache.trim_persistent(d, 1000) == 0


def test_disabled_path_is_zero_overhead():
    """With fusion off the pass must return before walking the tree —
    the one-branch-when-off contract shared with trace/metrics."""
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.exec.wholestage import fuse_whole_stages

    class Untouchable:
        @property
        def children(self):          # pragma: no cover - must not run
            raise AssertionError("disabled fusion pass walked the tree")
    node = Untouchable()
    conf = TpuConf(FUSION_OFF)
    assert fuse_whole_stages(node, conf) is node
    s = tpu_session(FUSION_OFF)
    out = _chain(s.create_dataframe(_table())).explain("physical")
    assert "WholeStage" not in out


def test_rect_chain_kernel_is_process_wide():
    """The byte-rectangle string kernels must resolve through the
    executable cache (per-exec dicts re-traced every query — the
    string_transforms_100k warm cliff)."""
    from spark_rapids_tpu.api.functions import col, upper
    from spark_rapids_tpu.exprs.compiler import compile_rect_chain
    e = upper(col("s")).expr
    fn1 = compile_rect_chain(e, 16, 1024, 64)
    fn2 = compile_rect_chain(e, 16, 1024, 64)
    assert fn1 is fn2
    assert compile_rect_chain(e, 32, 1024, 64) is not fn1


# ---------------------------------------------------------------------------
# cost-model feedback + placement reason
# ---------------------------------------------------------------------------

def test_fused_stage_walls_feed_the_cost_model(monkeypatch):
    from spark_rapids_tpu.plan import cost
    monkeypatch.setitem(cost._OP_COSTS, ("WholeStageExec", "device"),
                        (1 << 20, 0.001))
    lc = cost.learned_row_cost("WholeStageExec", "device")
    assert lc is not None and lc < 1e-8
    # under the min-rows threshold the learned cost is not trusted
    monkeypatch.setitem(cost._OP_COSTS, ("tiny", "device"), (10, 5.0))
    assert cost.learned_row_cost("tiny", "device") is None


def test_op_costs_persist_roundtrip(tmp_path, monkeypatch):
    import importlib
    from spark_rapids_tpu.plan import stats_store
    monkeypatch.setenv("SRTPU_STATS_PERSIST", "1")
    monkeypatch.setenv("SRTPU_STATS_PATH", str(tmp_path / "stats.json"))
    from spark_rapids_tpu.plan import cost
    monkeypatch.setattr(stats_store, "_loaded", False)
    monkeypatch.setattr(stats_store, "_dirty", True)
    monkeypatch.setitem(cost._OP_COSTS, ("WholeStageExec", "device"),
                        (123456, 0.5))
    stats_store.save()
    walls, rows, ops = {}, {}, {}
    monkeypatch.setattr(stats_store, "_loaded", False)
    stats_store.load_into(walls, rows, ops)
    assert ops[("WholeStageExec", "device")] == (123456, 0.5)


def test_wholestage_records_device_wall(monkeypatch):
    from spark_rapids_tpu.plan import cost
    # under the per-query sample gate nothing is learned: a 4096-row
    # region measures dispatch floor, not per-row cost
    before = cost._OP_COSTS.get(("WholeStageExec", "device"), (0, 0.0))
    s = tpu_session()
    _chain(s.create_dataframe(_table(4096))).collect_arrow()
    assert cost._OP_COSTS.get(("WholeStageExec", "device"),
                              (0, 0.0)) == before
    # at scale (gate lowered so the test stays fast) the fused region
    # feeds its measured device wall into the learned table
    monkeypatch.setattr(cost, "_OP_COST_SAMPLE_MIN_ROWS", 1024)
    _chain(s.create_dataframe(_table(4096))).collect_arrow()
    after = cost._OP_COSTS.get(("WholeStageExec", "device"), (0, 0.0))
    assert after[0] >= before[0] + 4096
    assert after[1] > before[1]


def test_explain_prints_placement_reason():
    s = tpu_session({"spark.rapids.tpu.sql.optimizer.enabled": True})
    out = _chain(s.create_dataframe(_table(64))).explain("physical")
    assert out.startswith("placement: ")
    head = out.splitlines()[0]
    assert "host (" in head or "device (" in head


# ---------------------------------------------------------------------------
# srtpu_compile_* metrics
# ---------------------------------------------------------------------------

def test_compile_metrics_are_declared_and_recorded():
    from spark_rapids_tpu.metrics import shutdown_metrics
    from spark_rapids_tpu.metrics.registry import (MetricRegistry,
                                                   install_metrics,
                                                   metric_inventory)
    inv = metric_inventory()
    for name in ("srtpu_compile_cache_hits_total",
                 "srtpu_compile_cache_misses_total",
                 "srtpu_compile_persistent_hits_total",
                 "srtpu_compile_seconds_total"):
        assert name in inv and inv[name]["kind"] == "counter"
    reg = install_metrics(MetricRegistry())
    try:
        key = exec_cache.fused_key("metrics-" + os.urandom(4).hex(), ())
        exec_cache.get_or_build(key, lambda: (lambda: 0))
        exec_cache.get_or_build(key, lambda: (lambda: 0))
        snap = reg.snapshot()
        assert snap["srtpu_compile_cache_misses_total"]["series"][0][
            "value"] >= 1
        assert snap["srtpu_compile_cache_hits_total"]["series"][0][
            "value"] >= 1
    finally:
        shutdown_metrics()


# ---------------------------------------------------------------------------
# adhoc-jit lint rule
# ---------------------------------------------------------------------------

def _jit_findings(src, rel):
    from spark_rapids_tpu.tools.lint import AdHocJitRule
    from spark_rapids_tpu.tools.lint.framework import FileContext
    ctx = FileContext(rel, src, rel=rel)
    assert ctx.parse_error is None
    return [f for f in AdHocJitRule().check(ctx) if not ctx.suppressed(f)]


JIT_SRC = """
import functools
import jax

@jax.jit
def decorated(x):
    return x

@functools.partial(jax.jit, static_argnums=(1,))
def partial_decorated(x, n):
    return x

def builder():
    return jax.jit(lambda x: x)
"""


def test_adhoc_jit_rule_flags_unblessed_modules():
    fs = _jit_findings(JIT_SRC, "spark_rapids_tpu/exec/somewhere.py")
    assert len(fs) == 3, [repr(f) for f in fs]
    assert all(f.rule == "adhoc-jit" for f in fs)
    # keys are line-free (baseline survives unrelated edits)
    for f in fs:
        assert str(f.line) not in f.key


def test_adhoc_jit_rule_blesses_compiler_and_cache():
    for rel in ("spark_rapids_tpu/exprs/compiler.py",
                "spark_rapids_tpu/plan/exec_cache.py"):
        assert _jit_findings(JIT_SRC, rel) == []
    # files outside the package (tests, tools) are not checked
    assert _jit_findings(JIT_SRC, "tests/test_x.py") == []


def test_adhoc_jit_rule_suppression():
    src = ("import jax\n"
           "fn = jax.jit(lambda x: x)  # tpulint: disable=adhoc-jit\n")
    assert _jit_findings(src, "spark_rapids_tpu/exec/x.py") == []


def test_tree_has_no_new_adhoc_jit_findings():
    """The checked-in baseline covers every grandfathered jax.jit site;
    new ones must go through the executable cache."""
    import spark_rapids_tpu
    from spark_rapids_tpu.tools.lint import AdHocJitRule, run_lint
    from spark_rapids_tpu.tools.lint.framework import load_baseline
    pkg = os.path.dirname(spark_rapids_tpu.__file__)
    res = run_lint([pkg], rules=[AdHocJitRule()],
                   baseline=load_baseline())
    assert res.ok, [repr(f) for f in res.new]
