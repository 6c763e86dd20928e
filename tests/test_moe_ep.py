"""Expert-parallel shard_map MoE dispatch vs the dense GSPMD reference.

The equivalence test runs in a subprocess with 8 host devices (the
device count is locked at first jax init, so it cannot run in-process)
and dropless capacities, where EP and the sort-based dispatch must agree
to fp tolerance.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe_ep import _pack

ROOT = Path(__file__).resolve().parents[1]


def _subprocess_env():
    """Inherit the environment (JAX_PLATFORMS=cpu etc. — a bare env
    makes jax probe for TPUs for minutes) but pin PYTHONPATH and drop
    any outer XLA_FLAGS so the script controls the device count."""
    import os

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


class TestPack:
    def test_pack_roundtrip_no_drops(self):
        ids = jnp.array([2, 0, 1, 2, 0, 1, 1, 3])
        vals = jnp.arange(8.0)[:, None] * jnp.ones((8, 3))
        bufs, slot = _pack(ids, 4, 3, {"x": vals})
        flat = jnp.concatenate(
            [bufs["x"].reshape(-1, 3), jnp.zeros((1, 3))], axis=0)
        np.testing.assert_allclose(flat[slot], vals)  # full inversion

    def test_pack_drops_overflow(self):
        ids = jnp.zeros((5,), jnp.int32)  # all to bin 0, cap 2
        vals = jnp.arange(5.0)[:, None]
        bufs, slot = _pack(ids, 2, 2, {"x": vals})
        assert int((slot == 2 * 2).sum()) == 3  # 3 dropped
        kept = bufs["x"].reshape(-1)[:2]
        assert set(np.asarray(kept)) <= set(range(5))

    def test_pack_valid_mask(self):
        ids = jnp.array([0, 1, 0, 1])
        valid = jnp.array([True, False, True, True])
        bufs, slot = _pack(ids, 2, 2, {"x": jnp.ones((4, 1))}, valid=valid)
        assert int(slot[1]) == 2 * 2  # invalid -> sentinel
        assert float(bufs["x"].sum()) == 3.0


EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models import moe
    from repro.sharding.rules import ShardingRules, use_rules

    cfg = get_arch("qwen3-moe-30b-a3b").reduced()
    cfg = dataclasses.replace(cfg, num_experts=8, experts_per_token=2,
                              moe_capacity_factor=float(8 // 2))  # dropless
    key = jax.random.PRNGKey(0)
    params = moe.moe_init(key, cfg)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                                jnp.float32).astype(jnp.bfloat16)

    mesh = make_mesh((4, 2), ("data", "model"))
    rules_ep = ShardingRules(batch="data", seq=None, embed=None,
                             expert="data", expert_mlp="model",
                             embed_fsdp=None, mlp="model", moe_ep=True)
    rules_ref = ShardingRules(rules_ep, moe_ep=False)

    outs = {}
    for name, rules in (("ep", rules_ep), ("ref", rules_ref)):
        def f(p, x):
            with use_rules(rules, mesh):
                return moe.moe_apply(p, x, cfg)
        with jax.set_mesh(mesh):
            y, aux = jax.jit(f)(params, x)
        outs[name] = (np.asarray(y, np.float32), float(aux))

    y_ep, aux_ep = outs["ep"]
    y_ref, aux_ref = outs["ref"]
    err = np.abs(y_ep - y_ref).max()
    print("MAXERR", err, "AUX", abs(aux_ep - aux_ref))
    assert err < 5e-2, err                       # bf16 accumulation order
    assert abs(aux_ep - aux_ref) < 1e-3
    print("EP-EQUIV-OK")
""")


@pytest.mark.slow
def test_ep_matches_dense_dispatch_8dev():
    res = subprocess.run(
        [sys.executable, "-c", EQUIV_SCRIPT],
        env=_subprocess_env(),
        capture_output=True, text=True, timeout=600)
    assert "EP-EQUIV-OK" in res.stdout, res.stdout + res.stderr


@pytest.mark.slow
def test_ep_grad_flows_8dev():
    script = EQUIV_SCRIPT.replace(
        'assert err < 5e-2, err',
        'assert err < 5e-2, err\n'
        '    # grad through the EP path\n')
    grad_script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_arch
        from repro.launch.mesh import make_mesh
        from repro.models import moe
        from repro.sharding.rules import ShardingRules, use_rules

        cfg = get_arch("qwen3-moe-30b-a3b").reduced()
        cfg = dataclasses.replace(cfg, num_experts=8, experts_per_token=2,
                                  moe_capacity_factor=4.0)
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                    (8, 16, cfg.d_model))
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = ShardingRules(batch="data", expert="data",
                              expert_mlp="model", mlp="model", moe_ep=True)

        def loss(p, x):
            with use_rules(rules, mesh):
                y, aux = moe.moe_apply(p, x, cfg)
            return (y.astype(jnp.float32) ** 2).mean() + 0.01 * aux

        with jax.set_mesh(mesh):
            g = jax.jit(jax.grad(loss))(params, x)
        total = sum(float(jnp.abs(l.astype(jnp.float32)).sum())
                    for l in jax.tree.leaves(g))
        assert total > 0 and np.isfinite(total)
        wi_g = float(jnp.abs(g["wi"].astype(jnp.float32)).sum())
        assert wi_g > 0  # grads reach the expert weights through a2a
        print("EP-GRAD-OK", total)
    """)
    res = subprocess.run(
        [sys.executable, "-c", grad_script],
        env=_subprocess_env(),
        capture_output=True, text=True, timeout=600)
    assert "EP-GRAD-OK" in res.stdout, res.stdout + res.stderr
