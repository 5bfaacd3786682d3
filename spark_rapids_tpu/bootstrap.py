"""Engine bootstrap: environment validation + lifecycle diagnostics.

Reference analog: the plugin's driver/executor startup path
(Plugin.scala — RapidsDriverPlugin.init:418, RapidsExecutorPlugin
init/arch checks:488-568, shutdown hooks:479/649, version banner and
mismatch errors:50-120). Standalone engine shape: no Spark plugin
registry to hook, so the checks run at session start (opt-in via
``check_environment`` / ``spark.rapids.tpu.startupCheck.enabled``) and
shutdown behavior lives on ``TpuSession.close`` (leak audit) plus the
process-exit cache flush jax owns.

Every check returns a record instead of printing, so callers (tests,
the driver, a user diagnosing a deploy) can assert on them; FATAL
findings raise ``EnvironmentProblem`` only when ``strict=True`` — the
reference similarly distinguishes hard version mismatches from
warnings.
"""
from __future__ import annotations

from typing import Dict, List

from .config import TpuConf, register

__all__ = ["check_environment", "EnvironmentProblem", "engine_banner",
           "STARTUP_CHECK"]

STARTUP_CHECK = register(
    "spark.rapids.tpu.startupCheck.enabled", False,
    "Run the environment validation (bootstrap.check_environment) when "
    "a session is created, logging findings: backend platform and "
    "device count, x64 mode, compile-cache writability, memory-pool "
    "conf sanity, suspicious conf combinations (ref Plugin.scala "
    "executor startup checks:488-568).")


class EnvironmentProblem(RuntimeError):
    """A FATAL environment finding under strict checking (the
    CudfVersionMismatchException analog, Plugin.scala:50)."""


def engine_banner() -> str:
    import jax

    from .version import __version__
    try:
        devs = jax.devices()
        plat = devs[0].platform
        nd = len(devs)
    except RuntimeError:
        plat, nd = "unavailable", 0
    return (f"spark-rapids-tpu {__version__} on jax {jax.__version__} "
            f"[{plat} x{nd}]")


def check_environment(conf: TpuConf = None, strict: bool = False) -> List[Dict]:
    """Validate the runtime the way the reference validates executors at
    startup. Returns [{check, level(ok|warn|fatal), detail}]; raises
    EnvironmentProblem on fatal findings when ``strict``."""
    import os

    import jax

    conf = conf or TpuConf()
    out: List[Dict] = []

    def rec(check: str, level: str, detail: str):
        out.append({"check": check, "level": level, "detail": detail})

    # --- backend / devices (GpuDeviceManager analog) -------------------
    try:
        devs = jax.devices()
        rec("backend", "ok",
            f"{devs[0].platform} x{len(devs)} ({type(devs[0]).__name__})")
        if devs[0].platform == "cpu":
            rec("accelerator", "warn",
                "no accelerator backend: the engine runs, but device "
                "placement will never win against the host baseline")
    except RuntimeError as e:
        rec("backend", "fatal", f"no jax backend initializes: {e}")

    # --- numerics mode --------------------------------------------------
    if jax.config.jax_enable_x64:
        rec("x64", "ok", "int64/float64 enabled (Spark parity mode)")
    else:
        rec("x64", "fatal",
            "jax_enable_x64 is OFF: bigint/double columns would "
            "silently truncate — import spark_rapids_tpu before "
            "flipping jax config")

    # --- compile cache (the fatbin-cache analog) -----------------------
    cache = jax.config.jax_compilation_cache_dir
    if not cache:
        rec("compile_cache", "warn",
            "persistent compile cache disabled: first-ever kernel "
            "compiles repeat every process (sort-bearing kernels are "
            "the slowest)")
    else:
        try:
            os.makedirs(cache, exist_ok=True)
            probe = os.path.join(cache, ".srtpu_probe")
            with open(probe, "w") as f:
                f.write("ok")
            os.remove(probe)
            from . import compile_cache_dir_is_external
            rec("compile_cache", "ok",
                cache + (" (from JAX_COMPILATION_CACHE_DIR)"
                         if compile_cache_dir_is_external() else ""))
        except OSError as e:
            rec("compile_cache", "warn",
                f"cache dir {cache} not writable ({e}): compiles "
                "will not persist")

    # --- memory pool sanity (GpuDeviceManager pool checks) -------------
    from .config import ALLOC_FRACTION, HBM_LIMIT_BYTES
    frac = float(conf.get(ALLOC_FRACTION))
    limit = int(conf.get(HBM_LIMIT_BYTES))
    if not 0.0 < frac <= 1.0:
        rec("memory_pool", "fatal",
            f"memory.hbm.allocFraction {frac} outside (0, 1]")
    else:
        rec("memory_pool", "ok",
            f"allocFraction {frac}" + (
                f", explicit limit {limit >> 20} MiB" if limit
                else ", limit derived from device"))

    # --- conf combination lint ----------------------------------------
    from .io.device_decode import DEVICE_DECODE_ENABLED
    from .config import PARQUET_READER_TYPE
    rt = str(conf.get(PARQUET_READER_TYPE)).upper()
    if bool(conf.get(DEVICE_DECODE_ENABLED)) \
            and rt not in ("PERFILE", "AUTO"):
        # AUTO resolves to PERFILE for single-file scans, so only the
        # explicitly-incompatible modes warrant the warning
        rec("conf", "warn",
            f"io.parquet.deviceDecode.enabled is on but reader.type="
            f"{rt} never takes the per-file path the decode requires")

    if strict and any(r["level"] == "fatal" for r in out):
        bad = [r for r in out if r["level"] == "fatal"]
        raise EnvironmentProblem("; ".join(
            f"{r['check']}: {r['detail']}" for r in bad))
    return out
