"""spark-rapids-tpu: a TPU-native columnar SQL execution framework.

A ground-up re-design of the capabilities of the RAPIDS Accelerator for Apache
Spark (reference: /root/reference, spark-rapids 24.12) for TPU hardware:
columnar batches are shape-bucketed jax.Arrays in HBM, operators compile to
XLA computations (jax.numpy), distribution rides jax.sharding meshes
with ICI/DCN collectives, and a tiered HBM->host->disk memory runtime provides
spill + OOM-retry semantics.
"""

import jax as _jax

# Spark semantics require real int64/float64 columns (bigint/double).
# On TPU f64 is software-emulated by XLA; the planner prefers f32/bf16 where
# the user opts into approximate float, but parity mode needs x64 on.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: sort-bearing kernels take the longest
# to compile, and the cache makes that a once-per-checkout cost (the analog
# of the reference shipping precompiled fatbins per architecture). Where it
# lives is decided from OUTSIDE: when JAX_COMPILATION_CACHE_DIR is set, jax
# reads it itself and no repo code sets a directory. Otherwise it is ONE
# fixed directory inside the checkout — the path is part of nothing that
# moves (no fingerprint, pid, time or temp name), so every process of a
# checkout shares it.
import os as _os

#: learned state the engine writes beside the sources (git-ignored): the
#: default compile cache below and the adaptive-stats file
#: (plan/stats_store.py)
STATE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".srtpu_cache")
DEFAULT_COMPILE_CACHE_DIR = _os.path.join(STATE_DIR, "xla")


def compile_cache_dir_is_external() -> bool:
    """True when the environment placed the compile cache: repo code
    (this module, plan/exec_cache.configure_from_conf) then sets none."""
    return bool(_os.environ.get("JAX_COMPILATION_CACHE_DIR"))


if not compile_cache_dir_is_external():
    _jax.config.update("jax_compilation_cache_dir",
                       DEFAULT_COMPILE_CACHE_DIR)
# every executable is persisted, however quick its compile: a second
# process over the same queries then pays trace time only
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

from .version import __version__
from .types import Schema, StructField
from .columnar import ColumnarBatch, DeviceColumn, HostColumn
from .config import TpuConf

__all__ = ["__version__", "Schema", "StructField", "ColumnarBatch",
           "DeviceColumn", "HostColumn", "TpuConf"]
