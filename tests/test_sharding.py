"""Sharding rules + param-spec resolution, and a subprocess mini-mesh
lowering check (the full 512-device dry-run runs via launch/dryrun.py)."""
import subprocess
import sys
from pathlib import Path

import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import SINGLE_POD_FSDP_TP, SINGLE_POD_TP


class TestRules:
    def test_spec_resolution(self):
        spec = SINGLE_POD_TP.spec(("batch", "seq", "heads"))
        assert spec == P(None, None, "model")

    def test_spec_dedup(self):
        r = SINGLE_POD_FSDP_TP
        spec = r.spec(("expert", "embed_fsdp", "expert_mlp"))
        assert spec == P("data", None, "model")  # embed_fsdp dropped

    def test_unknown_logical_axis_replicates(self):
        assert SINGLE_POD_TP.spec(("nonexistent",)) == P(None)


class TestParamSpecs:
    def _mesh(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        return Mesh(dev, ("data", "model"))

    def test_divisibility_drop(self):
        """15 heads on a 16-way model axis -> replicated (no crash)."""
        from repro.sharding.param_specs import spec_for_path

        # faking a 16-wide model axis by reusing device 0 is not allowed;
        # directly exercise the divisibility logic with mesh.shape
        class FakeMesh:
            shape = {"data": 16, "model": 16}
        spec = spec_for_path("groups/b0/temporal/wq", (960, 15, 64),
                             SINGLE_POD_TP, FakeMesh())
        assert spec == P(None, None, None)  # heads 15 % 16 != 0
        spec = spec_for_path("groups/b0/mlp/wi", (960, 2560),
                             SINGLE_POD_TP, FakeMesh())
        assert spec == P(None, "model")     # 2560 % 16 == 0

    def test_moe_spec(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}
        from repro.sharding.param_specs import spec_for_path
        spec = spec_for_path("groups/b0/moe/wi", (2, 128, 2048, 768),
                             SINGLE_POD_FSDP_TP, FakeMesh())
        assert spec == P(None, "data", None, "model")

    def test_cache_spec(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}
        from repro.sharding.param_specs import spec_for_path
        # kv=16 divides the model axis -> head-sharded cache
        spec = spec_for_path("groups/b0/temporal/k", (16, 128, 32768, 16, 128),
                             SINGLE_POD_FSDP_TP, FakeMesh(), table="cache")
        assert spec == P(None, "data", None, "model", None)
        # kv=8 does NOT divide -> dropped (serve_rules then seq-shards
        # the cache over "model" instead, see launch/steps.py)
        spec = spec_for_path("groups/b0/temporal/k", (16, 128, 32768, 8, 128),
                             SINGLE_POD_FSDP_TP, FakeMesh(), table="cache")
        assert spec == P(None, "data", None, None, None)


MINI_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs.base import get_arch, InputShape
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_step
mesh = make_mesh((2, 4), ("data", "model"))
ok = []
for arch in ["smollm-360m", "qwen3-moe-30b-a3b", "recurrentgemma-9b"]:
    for shape in [InputShape("t", 128, 8, "train"),
                  InputShape("d", 256, 8, "decode")]:
        built = build_step(get_arch(arch).reduced(), shape, mesh)
        built.fn.lower(*built.args).compile()
        ok.append(f"{arch}:{shape.kind}")
print("LOWERED", len(ok))
"""


@pytest.mark.slow
def test_mini_mesh_lowering():
    """Reduced configs lower+compile on an 8-device (2x4) host mesh.
    Runs in a subprocess because the device count must be set before jax
    initializes."""
    env = {"PYTHONPATH": str(Path(__file__).parent.parent / "src"),
           "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS",)})
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src")
    out = subprocess.run([sys.executable, "-c", MINI_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert "LOWERED 6" in out.stdout, out.stderr[-2000:]
