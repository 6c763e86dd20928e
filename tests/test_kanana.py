"""Kanana-2-30B-A3B (MLA + DeepSeekMoE) at a reduced size on the CPU, on
seeded random weights: the program against the plain reference kept with
the benchmark (`bench/reference/kanana-2-30b-a3b.py`), the dropless MoE
against dense computation, the chip's shares against the uncut layer,
and the mesh path's next-token loss."""
import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.experiments import build, get_scenario, override
from repro.models import layers, moe
from repro.models.transformer import Transformer

BENCH = Path(__file__).resolve().parents[1] / "bench"
F32 = jnp.dtype("float32")


def _bench_module(rel: str, name: str):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))      # the reference imports `reference.rules`
    spec = importlib.util.spec_from_file_location(name, BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("reference/kanana-2-30b-a3b.py", "kanana_reference")


@pytest.fixture(scope="module")
def small():
    """(reduced ArchConfig, the configuration file's keys at its sizes):
    a dense layer and two MoE layers, 2 of 4 experts held."""
    engine = _bench_module("engines/mesh_moe.py", "kanana_engine")
    cfg = json.loads((BENCH / "configs" / "kanana-2-30b-a3b.json").read_text())
    arch = dataclasses.replace(get_arch("kanana-2-30b-a3b").reduced(),
                               num_layers=3)
    return arch, dict(cfg, **{k: getattr(arch, f)
                              for k, f in engine.ARCH_FIELDS.items()})


# ---------------------------------------------------------------------------
# chunked attention with a value width of its own
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, causal):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("hd_v", [24, 40])
@pytest.mark.parametrize("causal", [True, False])
def test_chunked_attention_value_width(hd_v, causal):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 40, 3, 24))
    k = jax.random.normal(ks[1], (2, 40, 3, 24))
    v = jax.random.normal(ks[2], (2, 40, 3, hd_v))
    with jax.default_matmul_precision("highest"):
        out = layers.chunked_attention(q, k, v, causal=causal, q_chunk=16,
                                       kv_chunk=8)
        want = _naive_attention(q, k, v, causal)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
        # the backward pass (rematerialised per query chunk) too
        f = lambda q, k, v, fn: jnp.sum(jnp.sin(fn(q, k, v)))
        g = jax.grad(f, argnums=(0, 1, 2))(
            q, k, v, lambda q, k, v: layers.chunked_attention(
                q, k, v, causal=causal, q_chunk=16, kv_chunk=8))
        g_want = jax.grad(f, argnums=(0, 1, 2))(
            q, k, v, lambda q, k, v: _naive_attention(q, k, v, causal))
    for a, b in zip(g, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the mesh path's loss
# ---------------------------------------------------------------------------

def test_smollm_norm_eps_is_published():
    assert get_arch("smollm-360m").norm_eps == 1e-5


def test_mesh_round_scores_position_t_on_token_t_plus_1():
    """The runner's global loss is the cross-entropy of the logits at
    position t against token t + 1 of the eval batch, by hand."""
    spec = override(get_scenario("mesh/smollm-smoke"), "model.seq_len=16",
                    "model.per_worker_batch=2")
    prep = build(spec)
    state, info, _ = prep.step(prep.state, prep.key)
    _, _, k2, _ = jax.random.split(prep.key, 4)
    cfg = prep.aux["arch_cfg"]
    tokens = jax.random.randint(k2, (2, 16), 0, cfg.vocab_size)
    logits, _ = prep.aux["model"].forward(state.global_params,
                                          {"tokens": tokens})
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    want = -np.mean([[float(logp[b, t, tokens[b, t + 1]])
                      for t in range(15)] for b in range(2)])
    np.testing.assert_allclose(float(info.global_loss), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------

def test_reference_init_is_the_program_init(ref, small):
    arch, cfg = small
    key = jax.random.PRNGKey(2**31 + 5)
    want = Transformer(arch).init(key)
    have = ref.init_params(key, cfg)
    assert jax.tree.structure(have) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(have), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_program_matches_reference(ref, small):
    """Logits, loss (cross-entropy + alpha x balance loss) and every
    gradient leaf, in float32 at highest precision."""
    arch, cfg = small
    model = Transformer(dataclasses.replace(arch, dtype="float32"))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          model.init(jax.random.PRNGKey(11)))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0,
                                arch.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    with jax.default_matmul_precision("highest"):
        logits, bal = model.forward(params, batch)
        want_logits, want_bal = ref.forward(params, tokens, cfg, F32)
        np.testing.assert_allclose(logits, want_logits, rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(bal, want_bal, rtol=1e-5)
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        want_loss, want_grads = jax.value_and_grad(ref.loss)(params, tokens,
                                                             cfg, F32)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5 * max(scale, 1e-6))
    # the correction bias is a leaf that receives no gradient
    assert not np.any(np.asarray(grads["groups"]["b0"]["moe"]["bias"]))


# ---------------------------------------------------------------------------
# DeepSeekMoE: dropless, the bias steers selection and not weights
# ---------------------------------------------------------------------------

def _moe_cfg(**kw):
    base = dataclasses.replace(get_arch("kanana-2-30b-a3b").reduced(),
                               dtype="float32", num_experts=8,
                               experts_per_token=3, experts_held=8,
                               expert_offset=0)
    return dataclasses.replace(base, **kw)


def _dense_moe(params, x, cfg):
    """Every held expert on every token, masked by the routing."""
    h = layers.rmsnorm(params["norm"], x, cfg.norm_eps)
    s = jax.nn.sigmoid(h @ params["router"])
    _, idx = jax.lax.top_k(s + params["bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    gate = (jax.nn.one_hot(idx, cfg.num_experts) * w[..., None]).sum(-2)
    gate = gate[..., cfg.expert_offset: cfg.expert_offset + cfg.held_experts]
    ffn = jnp.einsum("bsef,efd->bsed", jax.nn.silu(
        jnp.einsum("bsd,edf->bsef", h, params["wi"]))
        * jnp.einsum("bsd,edf->bsef", h, params["wu"]), params["wo"])
    y = jnp.einsum("bsed,bse->bsd", ffn, gate)
    sp = params["shared"]
    return y + (jax.nn.silu(h @ sp["wi"]) * (h @ sp["wu"])) @ sp["wo"], idx


def test_dropless_moe_matches_dense_with_bias():
    cfg = _moe_cfg(experts_held=4, expert_offset=2)
    params = moe.deepseek_moe_init(jax.random.PRNGKey(1), cfg)
    # a seeded bias that crowds the tokens onto two held experts: the
    # load a capacity-bound layer would drop
    params["bias"] = (0.3 * jax.random.normal(jax.random.PRNGKey(2), (8,))
                      ).at[jnp.array([3, 4])].add(5.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, _ = moe.deepseek_moe_apply(params, x, cfg)
        want, idx = _dense_moe(params, x, cfg)
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
        assert np.all(np.any(np.asarray(idx) == 3, -1))   # every token
        # the bias steers selection: without it the picks differ ...
        unbiased = dict(params, bias=jnp.zeros((8,)))
        _, idx0 = _dense_moe(unbiased, x, cfg)
        assert np.any(np.sort(idx0, -1) != np.sort(idx, -1))
        # ... and never the weights: they are the unbiased scores'
        h = layers.rmsnorm(params["norm"], x, cfg.norm_eps).reshape(-1, cfg.d_model)
        s, ids, w = moe.route_sigmoid(h, params, cfg)
        sel = jnp.take_along_axis(s, ids, -1)
        np.testing.assert_allclose(
            w, sel / sel.sum(-1, keepdims=True) * cfg.routed_scaling_factor,
            rtol=1e-6)


# ---------------------------------------------------------------------------
# the chip's shares add up to the uncut layer
# ---------------------------------------------------------------------------

def _size(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def test_head_shares_add_up_to_uncut_mla():
    cfg = dataclasses.replace(get_arch("kanana-2-30b-a3b").reduced(),
                              dtype="float32")
    full = layers.mla_init(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))
    H, n = cfg.num_heads, 2
    hs = H // n
    per = dataclasses.replace(cfg, num_heads=hs)
    shares = [dict(full, wq=full["wq"][:, j * hs:(j + 1) * hs],
                   wkvb=full["wkvb"][:, j * hs:(j + 1) * hs],
                   wo=full["wo"][j * hs:(j + 1) * hs]) for j in range(n)]
    with jax.default_matmul_precision("highest"):
        total = sum(layers.mla_apply(p, x, per) for p in shares)
        np.testing.assert_allclose(total, layers.mla_apply(full, x, cfg),
                                   rtol=1e-5, atol=1e-5)
    # W_kva and the norms are whole on every chip: counted once
    whole = _size({k: full[k] for k in ("norm", "wkva", "kv_norm")})
    assert sum(_size(p) for p in shares) - (n - 1) * whole == _size(full)


def test_expert_shares_add_up_to_uncut_moe():
    cfg = _moe_cfg()
    full = moe.deepseek_moe_init(jax.random.PRNGKey(6), cfg)
    full["bias"] = 0.5 * jax.random.normal(jax.random.PRNGKey(7), (8,))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model))
    G = 2
    shares, parts = [], []
    with jax.default_matmul_precision("highest"):
        for j in range(cfg.num_experts // G):
            c = dataclasses.replace(cfg, experts_held=G, expert_offset=j * G)
            p = dict(full, **{k: full[k][j * G:(j + 1) * G]
                              for k in ("wi", "wu", "wo")})
            if j:                          # the shared experts: counted once
                del p["shared"]
            shares.append(p)
            parts.append(moe.deepseek_moe_apply(p, x, c)[0])
        np.testing.assert_allclose(sum(parts),
                                   moe.deepseek_moe_apply(full, x, cfg)[0],
                                   rtol=1e-5, atol=1e-5)
    whole = _size({k: full[k] for k in ("norm", "router", "bias")})
    assert (sum(_size(p) for p in shares) - (len(shares) - 1) * whole
            == _size(full))


# ---------------------------------------------------------------------------
# the normal path
# ---------------------------------------------------------------------------

def test_runner_builds_and_steps_the_reduced_kanana_round():
    spec = override(get_scenario("mesh/kanana-w2-4k"), "model.reduced=true",
                     "model.seq_len=32")
    prep = build(spec)
    arch = prep.aux["arch_cfg"]
    assert arch.experts_held == 2 and arch.num_experts == 4
    assert arch.first_k_dense == 1 and "dense0" in prep.aux["params"]
    state, info, _ = prep.step(prep.state, prep.key)
    assert np.all(np.isfinite(np.asarray(info.losses)))
    assert float(info.global_loss) > 0
    assert int(state.round_idx) == 1


def test_full_size_share_counts_362m_parameters():
    cfg = get_arch("kanana-2-30b-a3b")
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 362_046_464
    norms = cfg.num_layers * (2 * cfg.d_model) + cfg.d_model
    assert cfg.param_count() == n - norms
