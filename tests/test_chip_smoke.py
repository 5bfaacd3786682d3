"""CPU rehearsal of ``chip_smoke.py``: every phase at a tiny size.

The script itself refuses to run without a TPU (first test). Its phases
are plain functions, so the rehearsal imports them and passes the
platform its device arrays must live on — the only thing the script
would have taken from ``require_tpu``. The platform assertion is bypassed
HERE, by the test; the script has no option for it.
"""
import json

import numpy as np
import pytest

import chip_smoke as cs


def test_script_fails_without_a_tpu(capsys):
    """No accelerator: a non-zero exit before any phase, and no result
    line."""
    with pytest.raises(SystemExit) as ei:
        cs.main([])
    assert ei.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chunked_lineitem_reference_matches_one_shot(tmp_path):
    """Chunk i comes from (seed, i); the per-chunk pandas partials merge
    to what pandas says about the whole file read back."""
    import pyarrow.parquet as pq
    path = str(tmp_path / "lineitem.parquet")
    ref = cs.write_lineitem_parquet(path, 2500, seed=3, chunk_rows=1000)
    f = pq.ParquetFile(path)
    assert f.metadata.num_rows == 2500 and f.metadata.num_row_groups == 3
    whole = cs.LineitemReference()
    whole.add(f.read())
    np.testing.assert_allclose(ref.q6, whole.q6, rtol=1e-12)
    got, want = ref.q1_result(), whole.q1_result()
    assert list(got.index) == list(want.index)
    np.testing.assert_allclose(got.to_numpy(dtype=float),
                               want.to_numpy(dtype=float), rtol=1e-12)
    # a different seed is different data
    other = cs.write_lineitem_parquet(str(tmp_path / "o.parquet"), 2500,
                                      seed=4, chunk_rows=1000)
    assert other.q6 != ref.q6


def test_one_chip_phases_on_cpu(tmp_path, capsys):
    """Parquet scan -> q6, q1, q3 pinned to the device path, checked
    against pandas, warm repeats compile-free, device export on the
    backend's platform, zero OOM host fallbacks, default-settings pass."""
    report = cs.run_one_chip("cpu", rows=3000, ss_rows=3000, seed=11,
                             scratch=str(tmp_path / "scratch"))
    assert set(report) == {"tpch_q6", "tpch_q1", "tpcds_q3"}
    for name, r in report.items():
        assert r["runs"][-1]["cache"]["compile_s"] == 0, (name, r)
        assert r["default_placement"] in ("device", "host")
    out = capsys.readouterr().out
    assert "oom_state_machine=" in out
    assert "srtpu_oom_host_fallback_total=0" in out
    assert "BroadcastHashJoin" in out       # q3 ran the operator pipeline


def test_wrong_platform_fails_the_device_check(tmp_path):
    """The smoke does not trust a right answer: arrays on another
    platform than the asserted one fail the run."""
    import jax.numpy as jnp
    with pytest.raises(cs.SmokeFailure, match="lives on"):
        cs.assert_arrays_on("tpu", [jnp.zeros(4)], "rehearsal")


def test_four_chip_phase_on_virtual_devices(capsys):
    """--chips 4 on four of conftest's virtual CPU devices: q3 and the
    string-keyed aggregation show DistributedPipeline, every input shard
    and result on all four devices, an all-to-all in the compiled
    program, and the mesh answer equal to the one-device answer and
    pandas (all asserted inside the phase)."""
    import jax
    assert len(jax.devices()) >= 4
    cs.run_four_chips(4, seed=5)
    out = capsys.readouterr().out
    assert out.count("DistributedPipeline[n_dev=4") == 2
    assert out.count("on all 4 devices") == 2
    assert out.count("mesh result == one-chip result == pandas") == 2


def test_last_line_contract(monkeypatch, capsys):
    """What the driver parses: the LAST line is one JSON object with the
    device as jax reports it; a cut of the default size is printed on an
    earlier line; a failed phase prints no result line."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    seen = {}
    monkeypatch.setattr(cs, "require_tpu", lambda chips: dict(device))
    monkeypatch.setattr(cs, "run_one_chip",
                        lambda *a: seen.setdefault("args", a))
    assert cs.main(["--rows", "12345"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-1].startswith('{"ok": true, "device": {"platform": "tpu"')
    assert any(ln.startswith("[cut]") and "12345" in ln for ln in lines[:-1])
    assert seen["args"][:3] == ("tpu", 12345, cs.STORE_SALES_ROWS)

    def boom(*a):
        raise cs.SmokeFailure("phase failed")
    monkeypatch.setattr(cs, "run_one_chip", boom)
    with pytest.raises(cs.SmokeFailure):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out
