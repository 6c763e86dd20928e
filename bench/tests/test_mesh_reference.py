"""The SmolLM reference's model against the program's own model: on the
same weights and a next-token feed (labels = tokens, which
`Transformer.loss` shifts itself) at the published norm epsilon, both
compute the same loss. This is the witness that the reference's loss is
the model's, whatever the mesh runner feeds."""
import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import run
from conftest import BENCH


def test_reference_loss_matches_program_model():
    from repro.configs.base import get_arch
    from repro.models.transformer import Transformer
    cfg = json.loads((BENCH / "configs" / "smollm-360m.json").read_text())
    engine = run.load_module(BENCH / "engines" / "mesh.py", "bench_engine")
    ref = run.load_module(BENCH / "reference" / "smollm-360m.py",
                          "bench_reference")
    arch = dataclasses.replace(get_arch(cfg["expect"]["model.name"]).reduced(),
                               norm_eps=cfg["rms_norm_eps"])
    small = dict(cfg, **{k: getattr(arch, f)
                         for k, f in engine.ARCH_FIELDS.items()})
    key = jax.random.PRNGKey(2**31 + 5)
    params = jax.jit(functools.partial(ref.init_params, cfg=small))(key)
    model = Transformer(arch)
    assert (jax.tree.structure(params)
            == jax.tree.structure(model.init(key)))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0,
                                small["vocab_size"])
    prog = float(model.loss(params, {"tokens": tokens, "labels": tokens}))
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss(jax.tree.map(lambda a: a.astype(jnp.float32),
                                           params), tokens, small,
                              jnp.dtype("float32")))
    assert np.isfinite(want)
    assert abs(prog - want) / want < 5e-5, (prog, want)
