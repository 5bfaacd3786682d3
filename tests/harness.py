"""Differential test harness.

Reference analog: integration_tests asserts.py —
assert_gpu_and_cpu_are_equal_collect (:583) runs the same query lambda under
with_cpu_session / with_gpu_session and deep-compares. Here the two sessions
are the same planner with spark.rapids.tpu.sql.enabled toggled: the device
path runs fused XLA kernels, the CPU path runs the independent Arrow/pandas
host implementations — two independent engines, one oracle check.
"""
from __future__ import annotations

import math
from typing import Callable

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.config import TpuConf

DEFAULT_CONF = {}

#: the operator pipeline (exec/), not the one-program fragment of
#: parallel/ nor a host placement: a broadcast join stays a broadcast join
OPERATOR_CONF = {"spark.rapids.tpu.sql.optimizer.enabled": False,
                 "spark.rapids.tpu.sql.fusedPipeline.enabled": False,
                 "spark.rapids.tpu.distributed.enabled": False}


def tpu_session(extra_conf=None, mesh=None) -> TpuSession:
    conf = TpuConf({**DEFAULT_CONF, **(extra_conf or {})})
    return TpuSession(conf, mesh=mesh)


def cpu_session(extra_conf=None) -> TpuSession:
    conf = TpuConf({**DEFAULT_CONF, **(extra_conf or {}),
                    "spark.rapids.tpu.sql.enabled": False})
    return TpuSession(conf)


def _canon(df: pd.DataFrame, ignore_order: bool) -> pd.DataFrame:
    df = df.reset_index(drop=True)
    if ignore_order and len(df):
        df = df.sort_values(by=list(df.columns), na_position="first",
                            kind="mergesort").reset_index(drop=True)
    return df


def _assert_frames_equal(t: pd.DataFrame, c: pd.DataFrame,
                         approximate_float: bool):
    assert list(t.columns) == list(c.columns), (t.columns, c.columns)
    assert len(t) == len(c), f"row count {len(t)} != {len(c)}"
    for col in t.columns:
        tv, cv = t[col], c[col]
        tn = tv.isna().to_numpy()
        cn = cv.isna().to_numpy()
        np.testing.assert_array_equal(
            tn, cn, err_msg=f"null mask mismatch in column {col}")
        mask = ~tn
        if not mask.any():
            continue
        tvv = tv[mask].to_numpy()
        cvv = cv[mask].to_numpy()
        if np.issubdtype(np.asarray(tvv).dtype, np.floating):
            if approximate_float:
                np.testing.assert_allclose(
                    tvv.astype(np.float64), cvv.astype(np.float64),
                    rtol=1e-9, atol=1e-12, equal_nan=True,
                    err_msg=f"column {col}")
            else:
                np.testing.assert_array_equal(
                    tvv.astype(np.float64), cvv.astype(np.float64),
                    err_msg=f"column {col}")
        else:
            np.testing.assert_array_equal(tvv, cvv,
                                          err_msg=f"column {col}")


def assert_tpu_and_cpu_equal(query: Callable, ignore_order: bool = True,
                             approximate_float: bool = False,
                             conf: dict = None):
    """query: session -> DataFrame. Runs on both engines, compares."""
    t = query(tpu_session(conf)).to_pandas()
    c = query(cpu_session(conf)).to_pandas()
    _assert_frames_equal(_canon(t, ignore_order), _canon(c, ignore_order),
                         approximate_float)
    return t


def assert_tpu_fallback(query: Callable, fallback_exec: str,
                        conf: dict = None):
    """Assert the physical plan contains the expected CPU fallback exec
    (ref assert_gpu_fallback_collect, asserts.py:443)."""
    df = query(tpu_session(conf))
    physical = df._physical()
    tree = physical.tree_string()
    assert fallback_exec in tree, \
        f"expected {fallback_exec} in plan:\n{tree}"
    return assert_tpu_and_cpu_equal(query, conf=conf)


def assert_all_on_tpu(query: Callable, conf: dict = None):
    """Assert no CPU fallback nodes in the physical plan
    (ref validate_execs_in_gpu_plan marker)."""
    df = query(tpu_session(conf))
    tree = df._physical().tree_string()
    assert "!" not in tree, f"CPU fallback found in plan:\n{tree}"
