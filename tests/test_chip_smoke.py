"""chip_smoke.py's control flow on the CPU, at tiny sizes.

The script itself runs only on a TPU; here its phase functions run with
small overrides (the kernels in interpret mode), its four-device phase
runs on four virtual CPU devices, and its entry point must refuse to
report a result without a TPU or without the repository next to it.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY = ("data.num_workers=4", "data.n_local=64", "model.width_mult=2",
        "algo.local_epochs=1")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env():
    """Inherit the environment (JAX_PLATFORMS=cpu), minus any outer
    XLA_FLAGS, so a child controls its own device count."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def test_phase_paper(smoke):
    out = smoke.phase_paper(*TINY, rounds=2)
    assert out["finite"] and out["rounds"] == 2 and out["workers"] == 4
    assert 0.0 <= out["final_acc"] <= 1.0


def test_phase_wire_reads_kernel_dispatch_from_obs(smoke, tmp_path):
    out = smoke.phase_wire(*TINY, rounds=2, kernel_rows=512,
                           obs_dir=tmp_path)
    assert set(out["kernel_events"]) >= {"quant_pack_ef", "wire_agg"}
    assert out["backend"] == "cpu" and out["interpret"]
    # interpret mode is bit-identical to the references
    agree = out["kernels_vs_ref"]
    assert agree["quant_pack_ef_bit_identical"]
    assert agree["wire_agg_max_rel_diff"] == 0.0


def test_phase_mesh(smoke):
    out = smoke.phase_mesh("model.reduced=true", "model.seq_len=16",
                           "model.per_worker_batch=1", rounds=2)
    assert out["finite"] and len(out["worker_losses"]) == 2


def test_phase_sharded_on_four_devices():
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        out = chip_smoke.phase_sharded(4, full_width=False, seq_len=16)
        assert out["devices"] == 4, out
        print("SHARDED-OK", out["reduced_vs_one_device"])
    """)
    res = subprocess.run([sys.executable, "-c", script], env=_env(),
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED-OK" in res.stdout, res.stdout + res.stderr[-3000:]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_main_refuses_without_tpu_or_repo(where, tmp_path):
    """No TPU (this CPU run) or no repository beside the script: a
    non-zero exit and nothing on stdout."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = _env()
    if where == "alone":
        env.pop("PYTHONPATH")
    res = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=script.parent, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0, res.stdout
    assert res.stdout == "", res.stdout
