"""Plain reference of one swarm round of Kanana-2-30B-A3B on the mesh path,
at the chip's share of a 16-chip layer.

The model follows the published DeepSeek-V3 design (HF `deepseek_v3`
modelling code for this config), written here in plain `jax.numpy` and
computed in float32 at `highest` matmul precision from the bfloat16
weights the configuration states:

  * every layer: x + MLA(RMSNorm(x)), then + MLP (layer 0) or
    + DeepSeekMoE (the rest) of RMSNorm(x); final RMSNorm, untied head;
  * MLA without query compression: q = h W_q split per head into a
    no-RoPE and a RoPE part; [c, k_pe] = h W_kva, c = RMSNorm(c);
    [k_nope, v] = c W_kvb per head; RoPE rotates adjacent pairs
    (x[2i], x[2i+1]) of q_pe and of the one k_pe every head shares;
    causal softmax with scale (qk_nope + qk_rope) ** -0.5; W_o;
  * DeepSeekMoE: s = sigmoid(h W_r); the top k of s + b selected; each
    pick weighted s / sum(s of the picks) x the scaling factor; every
    held expert computed on every token and masked by that routing (no
    sort, no grouped products); plus the shared experts' gated MLP;
  * the loss: next-token cross-entropy over the vocabulary slice plus
    alpha x V3's sequence-wise balance loss of each MoE layer.

The share is the program's: the heads, experts and vocabulary rows the
configuration holds, built from the same seed. What the absent experts
and heads would add is left out, as in the program. The round is
M-DSL's (Algorithm 1): one local SGD step per worker, the Eq.-8 PSO
displacement, Eq.-5/6 selection, the masked delta mean of Eq. 7 over the
identity wire, and the Eq.-9/10 best tracking. Values the configuration
stores in bfloat16 (weights, velocities, the wire's deltas) are rounded
to bfloat16 where they are stored. It imports nothing of the program
under test.

To fit one chip beside nothing else, the round streams worker by
worker, each layer is recomputed in the backward pass and attention is
computed a block of queries at a time.

`dtype="float8_e4m3fn"` rounds every matmul operand to float8 (the
control of the output check); `fault=` plants one of `FAULTS`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import rules

# as for SmolLM: a negated upload leaves the norms the check compares
# unchanged for two near-orthogonal bf16 deltas
FAULTS = ("half_batch", "no_exchange")
# state arrays a round reads (the identity wire keeps no residual)
READS = ("params", "velocity", "best_params", "best_loss", "global", "gbest",
         "gbest_loss", "prev_theta_mean", "round_idx", "eta")
# carried state whose change in the round the check compares
CHANGES = ("global", "best", "gbest")
Q_BLOCK = 512            # queries per attention block


def _tmap(f, *t):
    return jax.tree.map(f, *t)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _dense(k, shape, fan_in, dt=jnp.bfloat16):
    return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dt)


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _mla_init(key, cfg):
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ks = jax.random.split(key, 4)
    return {"norm": _ones(d),
            "wq": _dense(ks[0], (d, h, dn + dr), d),
            "wkva": _dense(ks[1], (d, r + dr), d),
            "kv_norm": _ones(r),
            "wkvb": _dense(ks[2], (r, h, dn + dv), r),
            "wo": _dense(ks[3], (h * dv, d), h * dv).reshape(h, dv, d)}


def _mlp_init(key, d, ff):
    ks = jax.random.split(key, 3)
    return {"norm": _ones(d), "wi": _dense(ks[0], (d, ff), d),
            "wu": _dense(ks[1], (d, ff), d), "wo": _dense(ks[2], (ff, d), ff)}


def _moe_init(key, cfg):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E, G = cfg["router_experts"], cfg["n_routed_experts"]
    fs = cfg["n_shared_experts"] * f
    ks = jax.random.split(key, 7)
    return {"norm": _ones(d),
            "router": _dense(ks[0], (d, E), d, jnp.float32),
            "bias": jnp.zeros((E,), jnp.float32),
            "wi": _dense(ks[1], (G, d, f), d),
            "wu": _dense(ks[2], (G, d, f), d),
            "wo": _dense(ks[3], (G, f, d), f),
            "shared": {"wi": _dense(ks[4], (d, fs), d),
                       "wu": _dense(ks[5], (d, fs), d),
                       "wo": _dense(ks[6], (fs, d), fs)}}


def init_params(key, cfg: dict) -> dict:
    """Random weights: normal / sqrt(fan in) per matrix, 0.01 x normal
    embedding, unit norm scales, a zero correction bias; bfloat16
    matrices, the router in float32."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    keys = jax.random.split(key, 8)

    def layer(k):
        k1, _k2, k3 = jax.random.split(k, 3)
        return {"temporal": _mla_init(k1, cfg), "moe": _moe_init(k3, cfg)}

    def dense_layer(k):
        k1, _k2, k3 = jax.random.split(k, 3)
        return {"temporal": _mla_init(k1, cfg),
                "mlp": _mlp_init(k3, d, cfg["intermediate_size"])}

    n_dense = cfg["first_k_dense_replace"]
    params = {"embed": {"table": (jax.random.normal(keys[0], (V, d)) * 0.01
                                  ).astype(jnp.bfloat16)},
              "final_norm": _ones(d),
              "head": {"table": _dense(keys[4], (V, d), d)},
              "groups": jax.vmap(lambda k: {"b0": layer(
                  jax.random.split(k, 1)[0])})(jax.random.split(
                      keys[1], cfg["num_hidden_layers"] - n_dense))}
    for i in range(n_dense):
        params[f"dense{i}"] = dense_layer(jax.random.fold_in(keys[5], i))
    return params


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _q(x, dt):
    """Round a matmul operand to the compute precision (float32: as is)."""
    if dt == jnp.float32:
        return x
    return x.astype(dt).astype(jnp.float32)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """Rotate adjacent pairs (x[2i], x[2i+1]) of the last axis by
    position x theta ** (-2i / width). x: (B, S, ..., width)."""
    S, w = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, w, 2, dtype=jnp.float32) / w)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv          # (S, w/2)
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (w // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _mla(x, p, cfg, dt):
    mm = lambda spec, a, b: jnp.einsum(spec, _q(a, dt), _q(_f32(b), dt))
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    B, S, _ = x.shape
    a = _rms(p["norm"]["scale"], x, eps)
    q = mm("bsd,dhk->bshk", a, p["wq"])
    kva = mm("bsd,dk->bsk", a, p["wkva"])
    c = _rms(p["kv_norm"]["scale"], kva[..., :r], eps)
    kv = mm("bsr,rhk->bshk", c, p["wkvb"])
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], theta)], -1)
    k_pe = _rope_pairs(kva[..., r:], theta)                        # (B, S, dr)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None], kv.shape[:3] + k_pe.shape[-1:])], -1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(q.shape[-1])
    qn = min(Q_BLOCK, S)
    nb = S // qn

    @jax.checkpoint
    def block(args):
        qb, start = args                                  # (B, qn, H, k)
        s = mm("bqhd,bkhd->bhqk", qb, k) * scale
        causal = jnp.arange(S)[None, :] <= start + jnp.arange(qn)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (q.reshape(B, nb, qn, *q.shape[2:]).swapaxes(0, 1),
                            jnp.arange(nb) * qn))
    o = o.swapaxes(0, 1).reshape(B, S, *v.shape[2:])
    return mm("bshk,hkd->bsd", o, p["wo"])


def _mlp(a, p, dt):
    mm = lambda spec, x, w: jnp.einsum(spec, _q(x, dt), _q(_f32(w), dt))
    g = jax.nn.silu(mm("bsd,df->bsf", a, p["wi"])) * mm("bsd,df->bsf", a, p["wu"])
    return mm("bsf,fd->bsd", g, p["wo"])


def route(a, p, cfg):
    """(scores (B, S, E), selected ids (B, S, K), their weights) of the
    V3 router on normed activations a (float32)."""
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", a, p["router"]))
    _, idx = jax.lax.top_k(s + p["bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return s, idx, w


def _moe(x, p, cfg, dt):
    mm = lambda spec, a, b: jnp.einsum(spec, _q(a, dt), _q(_f32(b), dt))
    E, K = cfg["router_experts"], cfg["num_experts_per_tok"]
    off, G = cfg["expert_offset"], cfg["n_routed_experts"]
    S = x.shape[1]
    a = _rms(p["norm"]["scale"], x, cfg["rms_norm_eps"])
    s, idx, w = route(a, p, cfg)
    # sequence-wise balance loss: sum_i f_i P_i, mean over sequences
    chosen = jax.nn.one_hot(idx, E).sum(2)                         # (B, S, E)
    f = chosen.sum(1) * E / (K * S)
    P = (s / s.sum(-1, keepdims=True)).mean(1)
    bal = jnp.mean(jnp.sum(f * P, -1))
    # every held expert on every token, masked by the routing
    gate = (jax.nn.one_hot(idx, E) * w[..., None]).sum(2)[..., off:off + G]
    h = jax.nn.silu(mm("bsd,edf->bsef", a, p["wi"])) * mm("bsd,edf->bsef",
                                                          a, p["wu"])
    y = jnp.einsum("bsed,bse->bsd", mm("bsef,efd->bsed", h, p["wo"]), gate)
    return x + y + _mlp(a, p["shared"], dt), bal


def forward(params, tokens, cfg, dt):
    """(logits over the vocabulary slice (B, S, V), the MoE layers'
    summed balance losses) of a (B, S) token batch."""
    x = params["embed"]["table"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def dense_layer(x, p):
        x = x + _mla(x, p["temporal"], cfg, dt)
        m = p["mlp"]
        return x + _mlp(_rms(m["norm"]["scale"], x, cfg["rms_norm_eps"]), m, dt)

    for i in range(cfg["first_k_dense_replace"]):
        x = dense_layer(x, params[f"dense{i}"])

    def body(carry, p):
        x, bal = carry
        x = x + _mla(x, p["b0"]["temporal"], cfg, dt)
        x, b = _moe(x, p["b0"]["moe"], cfg, dt)
        return (x, bal + b), None

    (x, bal), _ = jax.lax.scan(jax.checkpoint(body), (x, jnp.float32(0.0)),
                               params["groups"])
    x = _rms(params["final_norm"]["scale"], x, cfg["rms_norm_eps"])
    return jnp.einsum("bsd,vd->bsv", _q(x, dt),
                      _q(_f32(params["head"]["table"]), dt)), bal


def loss(params, tokens, cfg, dt):
    """Mean next-token cross-entropy of a (B, S) token batch (position t
    scored against token t + 1, over the vocabulary slice) plus alpha x
    the MoE layers' balance losses."""
    logits, bal = forward(params, tokens, cfg, dt)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    ce = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
    return ce + cfg["balance_loss_alpha"] * bal


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------

def init(cfg: dict, spec: dict, seed: int, feed: dict) -> dict:
    """Round-1 state from the seed: every worker at one common init
    (made op by op, as the program makes it: a jitted init may round a
    weight's last bit differently)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    W = spec["data"]["num_workers"]
    stack = lambda t: _tmap(lambda x: jnp.broadcast_to(x, (W,) + x.shape), t)
    inf = jnp.float32(jnp.inf)
    return {"params": stack(params),
            "velocity": _tmap(jnp.zeros_like, stack(params)),
            "best_params": stack(params), "best_loss": jnp.full((W,), inf),
            "global": params, "gbest": params, "gbest_loss": inf,
            "prev_theta_mean": inf, "round_idx": jnp.int32(0),
            "eta": jnp.zeros((W,), jnp.float32)}


def _items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


@functools.partial(jax.jit, static_argnames=("cfg_items", "dt", "clip"))
def _local(w0, v, wl, wg, tokens, c0, c1, c2, lr, *, cfg_items, dt, clip):
    """One worker: one SGD step on its batch (weights stored in their
    configured dtype), then the Eq.-8 velocity and displaced model."""
    cfg = dict(cfg_items)
    g = jax.grad(loss)(_tmap(_f32, w0), tokens, cfg, jnp.dtype(dt))

    def leaf(w, vv, l, gb, gg):
        tr = (_f32(w) - lr * gg).astype(w.dtype)
        vn = (c0 * _f32(vv) + c1 * (_f32(l) - _f32(w))
              + c2 * (_f32(gb) - _f32(w)) + (_f32(tr) - _f32(w)))
        if clip > 0.0:
            vn = jnp.clip(vn, -clip, clip)
        vn = vn.astype(w.dtype)
        return (_f32(w) + _f32(vn)).astype(w.dtype), vn
    out = _tmap(leaf, w0, v, wl, wg, g)
    is_pair = lambda t: isinstance(t, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            jax.tree.map(lambda o: o[1], out, is_leaf=is_pair))


@functools.partial(jax.jit, static_argnames=("cfg_items", "dt"))
def _eval(params, tokens, *, cfg_items, dt):
    return loss(params, tokens, dict(cfg_items), jnp.dtype(dt))


def _sqnorms(tree):
    return [jnp.sum(jnp.square(_f32(l))) for l in jax.tree.leaves(tree)]


def run_round(view: dict, key, feed: dict, cfg: dict, spec: dict, *,
              dtype: str = "float32", fault: str | None = None,
              prog: dict | None = None, band: float = 0.0) -> dict:
    """One round from `view` (leaves may be host arrays) with the key the
    program's step was given. Returns the readings the check compares."""
    prec = "highest" if dtype == "float32" else "default"
    with jax.default_matmul_precision(prec):
        return _round(view, key, cfg, spec, dtype, fault, prog, band)


def _round(view, key, cfg, spec, dt, fault, prog, band):
    m, a = spec["model"], spec["algo"]
    hp = a["hp"]
    W, B, S = spec["data"]["num_workers"], m["per_worker_batch"], m["seq_len"]
    V = cfg["vocab_size"]
    items = _items(cfg)
    log = rules.new_log()
    get = lambda k: None if prog is None else prog[k]
    dev = lambda t: _tmap(jnp.asarray, t)
    worker = lambda t, i: _tmap(lambda x: jnp.asarray(x[i]), t)

    # the runner's step: batches and round key from the step's key; the
    # token ids are uniform over the vocabulary slice
    _, k1, k2, k3 = jax.random.split(key, 4)
    tokens = jax.random.randint(k1, (W, B, S), 0, V)
    eval_tokens = jax.random.randint(k2, (B, S), 0, V)
    if fault == "half_batch":
        tokens = tokens[:, : max(1, B // 2)] if B > 1 else tokens[:, :, : S // 2]
    ckey, _bkey, _qkey, _wkey = jax.random.split(k3, 4)

    def coeffs(k):
        c0k, c1k, c2k = jax.random.split(k, 3)
        return (jax.random.uniform(c0k, ()), jax.random.normal(c1k, ()),
                jax.random.normal(c2k, ()))
    cs = jax.vmap(coeffs)(jax.random.split(ckey, W))
    t = int(np.asarray(view["round_idx"]))
    lr = jnp.float32(hp["learning_rate"] * hp["lr_decay"]
                     ** (t // hp["lr_decay_every"]))
    clip = float(hp["velocity_clip"])

    g_in = dev(view["global"])
    gbest = dev(view["gbest"])
    new_params, vel_sq, losses = [], None, []
    for i in range(W):
        p, v = _local(worker(view["params"], i), worker(view["velocity"], i),
                      worker(view["best_params"], i), gbest, tokens[i],
                      cs[0][i], cs[1][i], cs[2][i], lr, cfg_items=items,
                      dt=dt, clip=clip)
        sq = _sqnorms(v)
        vel_sq = sq if vel_sq is None else [x + y for x, y in zip(vel_sq, sq)]
        del v
        losses.append(float(_eval(p, eval_tokens, cfg_items=items, dt=dt)))
        new_params.append(p)
    losses = np.asarray(losses, np.float32)

    tau = a["tau"]
    theta = tau * losses + (1 - tau) * np.asarray(view["eta"], np.float32)
    mask = rules.select(theta, float(np.asarray(view["prev_theta_mean"])),
                        get("mask"), get("theta"), band, log)

    # Eq. 7 over the identity wire: masked mean of the bf16 deltas
    total = _tmap(lambda g: jnp.zeros(g.shape, jnp.float32), g_in)
    for i in range(W):
        if mask[i] == 0:
            continue
        w_i = worker(view["params"], i)
        total = _tmap(lambda tot, n, o: tot + (n - o).astype(jnp.float32),
                      total, new_params[i], w_i)
        del w_i
    denom = max(float(mask.sum()), 1.0)
    g_out = _tmap(lambda g, s: (_f32(g) + s / denom).astype(g.dtype),
                  g_in, total)
    if fault == "no_exchange":
        g_out = g_in
    del total
    gl = float(_eval(g_out, eval_tokens, cfg_items=items, dt=dt))

    # Eq. 9 on F_{i,t+1} and Eq. 10 on the broadcast model
    improved = rules.decide(losses, np.asarray(view["best_loss"], np.float32),
                            get("local_improved"), get("losses"), band, log)
    g_imp = bool(rules.decide(gl, float(np.asarray(view["gbest_loss"])),
                              get("global_improved"), get("global_loss"),
                              band, log))
    diff = lambda a, b: [float(jnp.sqrt(x)) for x in _sqnorms(_tmap(
        lambda n, o: _f32(n) - _f32(o), a, b))]
    # Eq. 9 moves an improved worker's best to its new model
    best_sq = [0.0] * len(vel_sq)
    for i in np.flatnonzero(improved):
        best_sq = [x + y for x, y in zip(best_sq, _sqnorms(_tmap(
            lambda n, o: _f32(n) - jnp.asarray(o, jnp.float32),
            new_params[i], worker(view["best_params"], i))))]
    return {"losses": losses, "theta": theta.astype(np.float32),
            "global_loss": gl, "mask": mask.astype(np.float32),
            "local_improved": np.asarray(improved, bool),
            "global_improved": g_imp,
            "velocity_norms": [float(jnp.sqrt(x)) for x in vel_sq],
            "changes": {"global": diff(g_out, g_in),
                        "best": [float(jnp.sqrt(x)) for x in best_sq],
                        "gbest": (diff(g_out, gbest) if g_imp
                                  else [0.0] * len(vel_sq))},
            **log}
