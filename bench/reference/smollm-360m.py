"""Plain reference of one swarm round of SmolLM-360M on the mesh path.

The model is the published llama-architecture decoder (pre-norm RMSNorm,
RoPE with rotate-half, grouped-query causal softmax attention, gated SiLU
MLP, tied embedding head), written here in plain `jax.numpy` and
computed in float32 at `highest` matmul precision from the bfloat16
weights the configuration states. The round is M-DSL's (Algorithm 1):
one local SGD step per worker, the Eq.-8 PSO displacement, Eq.-5/6
selection, the masked delta mean of Eq. 7 over the identity wire, and
the Eq.-9/10 best tracking. Values the configuration stores in bfloat16
(weights, velocities, the wire's deltas) are rounded to bfloat16 where
they are stored. It imports nothing of the program under test.

To fit one chip beside nothing else, the round streams worker by
worker, and each layer is recomputed in the backward pass.

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

# a negated upload is not among them: the two workers' bf16 deltas are
# sparse and near orthogonal, so negating one leaves every norm the check
# compares unchanged (PERF.md, open questions)
FAULTS = ("half_batch", "no_exchange")
# state arrays a round reads (the identity wire keeps no residual)
READS = ("params", "velocity", "best_params", "best_loss", "global", "gbest",
         "gbest_loss", "prev_theta_mean", "round_idx", "eta")
# carried state whose change in the round the check compares
CHANGES = ("global", "best", "gbest")


def _tmap(f, *t):
    return jax.tree.map(f, *t)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def init_params(key, cfg: dict) -> dict:
    """Random weights: normal / sqrt(fan in) per matrix (fan in = first
    axis), 0.01 x normal embedding, unit norm scales; bfloat16 matrices."""
    d, ff, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    bf = jnp.bfloat16
    keys = jax.random.split(key, 8)

    def dense(k, shape):
        return (jax.random.normal(k, shape) / math.sqrt(shape[0])).astype(bf)

    def layer(gkey):
        k1, _k2, k3 = jax.random.split(jax.random.split(gkey, 1)[0], 3)
        ka = jax.random.split(k1, 5)
        km = jax.random.split(k3, 3)
        return {"b0": {
            "temporal": {"norm": {"scale": jnp.ones((d,), jnp.float32)},
                         "wq": dense(ka[0], (d, h, hd)),
                         "wk": dense(ka[1], (d, kv, hd)),
                         "wv": dense(ka[2], (d, kv, hd)),
                         "wo": dense(ka[3], (h, hd, d))},
            "mlp": {"norm": {"scale": jnp.ones((d,), jnp.float32)},
                    "wi": dense(km[0], (d, ff)), "wu": dense(km[1], (d, ff)),
                    "wo": dense(km[2], (ff, d))}}}

    return {"embed": {"table": (jax.random.normal(keys[0], (V, d)) * 0.01
                                ).astype(bf)},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "groups": jax.vmap(layer)(jax.random.split(
                keys[1], cfg["num_hidden_layers"]))}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _q(x, dt):
    """Round a matmul operand to the compute precision (float32: as is)."""
    if dt == jnp.float32:
        return x
    return x.astype(dt).astype(jnp.float32)


def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, cfg, dt):
    f32 = lambda a: a.astype(jnp.float32)
    eps, B, S = cfg["rms_norm_eps"], x.shape[0], x.shape[1]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mm = lambda spec, a, b: jnp.einsum(spec, _q(a, dt), _q(f32(b), dt))
    t = p["temporal"]
    a = _rms(t["norm"]["scale"], x, eps)
    q = _rope(mm("bsd,dhk->bshk", a, t["wq"]), cfg["rope_theta"])
    k = _rope(mm("bsd,dhk->bshk", a, t["wk"]), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", a, t["wv"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + mm("bshk,hkd->bsd", o, t["wo"])
    m = p["mlp"]
    a = _rms(m["norm"]["scale"], x, eps)
    g = jax.nn.silu(mm("bsd,df->bsf", a, m["wi"])) * mm("bsd,df->bsf", a, m["wu"])
    return x + mm("bsf,fd->bsd", g, m["wo"])


def loss(params, tokens, cfg, dt):
    """Mean next-token cross-entropy of a (B, S) token batch: position t
    is scored against token t + 1."""
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    body = jax.checkpoint(lambda x, p: (_layer(x, p["b0"], cfg, dt), None))
    x, _ = jax.lax.scan(body, x, params["groups"])
    x = _rms(params["final_norm"]["scale"], x, cfg["rms_norm_eps"])
    logits = jnp.einsum("bsd,vd->bsv", _q(x, dt),
                        _q(params["embed"]["table"].astype(jnp.float32), dt))
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    tgt = tokens[:, 1:]
    return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------

def init(cfg: dict, spec: dict, seed: int, feed: dict) -> dict:
    """Round-1 state from the seed: every worker at one common init."""
    params = jax.jit(functools.partial(init_params, cfg=cfg))(
        jax.random.PRNGKey(seed))
    W = spec["data"]["num_workers"]
    stack = lambda t: _tmap(lambda x: jnp.broadcast_to(x, (W,) + x.shape), t)
    inf = jnp.float32(jnp.inf)
    return {"params": stack(params),
            "velocity": _tmap(jnp.zeros_like, stack(params)),
            "best_params": stack(params), "best_loss": jnp.full((W,), inf),
            "global": params, "gbest": params, "gbest_loss": inf,
            "prev_theta_mean": inf, "round_idx": jnp.int32(0),
            "eta": jnp.zeros((W,), jnp.float32)}


@functools.partial(jax.jit, static_argnames=("cfg_items", "dt", "clip"))
def _local(w0, v, wl, wg, tokens, c0, c1, c2, lr, *, cfg_items, dt, clip):
    """One worker: one SGD step on its batch (weights stored in their
    configured dtype), then the Eq.-8 velocity and displaced model."""
    cfg = dict(cfg_items)
    g = jax.grad(loss)(_tmap(lambda a: a.astype(jnp.float32), w0), tokens,
                       cfg, jnp.dtype(dt))

    def leaf(w, vv, l, gb, gg):
        tr = (w.astype(jnp.float32) - lr * gg).astype(w.dtype)
        f = lambda a: a.astype(jnp.float32)
        vn = (c0 * f(vv) + c1 * (f(l) - f(w)) + c2 * (f(gb) - f(w))
              + (f(tr) - f(w)))
        if clip > 0.0:
            vn = jnp.clip(vn, -clip, clip)
        vn = vn.astype(w.dtype)
        return (f(w) + f(vn)).astype(w.dtype), vn
    out = _tmap(leaf, w0, v, wl, wg, g)
    is_pair = lambda t: isinstance(t, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            jax.tree.map(lambda o: o[1], out, is_leaf=is_pair))


@functools.partial(jax.jit, static_argnames=("cfg_items", "dt"))
def _eval(params, tokens, *, cfg_items, dt):
    return loss(params, tokens, dict(cfg_items), jnp.dtype(dt))


def _sqnorms(tree):
    return [jnp.sum(jnp.square(l.astype(jnp.float32)))
            for l in jax.tree.leaves(tree)]


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
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float)) and not isinstance(v, bool)))
    log = rules.new_log()
    get = lambda k: None if prog is None else prog[k]
    dev = lambda t: _tmap(jnp.asarray, t)
    worker = lambda t, i: _tmap(lambda x: jnp.asarray(x[i]), t)

    # the runner's step: batches and round key from the step's key
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
    g_out = _tmap(lambda g, s: (g.astype(jnp.float32) + s / denom).astype(g.dtype),
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
        lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32), a, b))]
    # Eq. 9 moves an improved worker's best to its new model
    best_sq = [0.0] * len(vel_sq)
    for i in np.flatnonzero(improved):
        best_sq = [x + y for x, y in zip(best_sq, _sqnorms(_tmap(
            lambda n, o: n.astype(jnp.float32) - jnp.asarray(o, jnp.float32),
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
