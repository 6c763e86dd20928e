"""Plain reference of one M-DSL communication round of the paper CNN fleet.

Written from the paper (Algorithm 1, Eqs. 3-10, Section V-A) and the wire
format's published block layout, in plain `jax.numpy` at float32 with
`highest` matmul precision. It imports nothing of the program under
test. It takes from the harness only:

  * the swarm state at the round's start, as a dict of named arrays
    (`init` builds round 1's from the seed itself);
  * the key handed to the round, and the fleet's data (the feed);
  * the program's readings of the same round, whose discrete decisions
    it adopts on near ties only (`rules.py`).

`dtype="bfloat16"` runs the same round with every array in bfloat16:
the control of the output check.  `fault=` plants one of the check's
faults (see `FAULTS`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import rules

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("half_batch", "no_exchange", "flip_upload", "invert_select",
          "no_feedback")
# carried state whose change in the round the check compares
CHANGES = ("global", "best", "gbest", "residual", "ps_residual")
BLOCK_ROWS, LANES = 256, 128
DOWNLINK_SALT = 0xD0


# ---------------------------------------------------------------------------
# model: 5-layer CNN (conv-pool, conv-pool, conv, dense, dense)
# ---------------------------------------------------------------------------

def init_params(key, width_mult, channels, num_classes, height, width):
    c1, c2, c3 = width_mult, 2 * width_mult, 2 * width_mult
    feat = (height // 4) * (width // 4) * c3
    hidden = 4 * width_mult
    ks = jax.random.split(key, 5)

    def conv(k, cin, cout):
        fan_in = 9 * cin
        return {"w": jax.random.normal(k, (3, 3, cin, cout))
                * jnp.sqrt(2.0 / fan_in), "b": jnp.zeros((cout,))}

    def dense(k, din, dout):
        return {"w": jax.random.normal(k, (din, dout)) * jnp.sqrt(2.0 / din),
                "b": jnp.zeros((dout,))}

    return {"conv1": conv(ks[0], channels, c1), "conv2": conv(ks[1], c1, c2),
            "conv3": conv(ks[2], c2, c3), "fc1": dense(ks[3], feat, hidden),
            "fc2": dense(ks[4], hidden, num_classes)}


def _conv(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    return y + p["b"]


def _pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(p, x):
    x = _pool(jax.nn.relu(_conv(p["conv1"], x)))
    x = _pool(jax.nn.relu(_conv(p["conv2"], x)))
    x = jax.nn.relu(_conv(p["conv3"], x))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1"]["w"], precision=HIGHEST) + p["fc1"]["b"])
    return jnp.dot(x, p["fc2"]["w"], precision=HIGHEST) + p["fc2"]["b"]


def xent(p, x, y, num_classes):
    logp = jax.nn.log_softmax(forward(p, x), axis=-1)
    return -(jax.nn.one_hot(y, num_classes, dtype=logp.dtype) * logp).sum(-1).mean()


def rmse(p, x, y, num_classes):
    """Eq. 3: per-sample RMSE between softmax output and one-hot label."""
    probs = jax.nn.softmax(forward(p, x), axis=-1)
    err = probs - jax.nn.one_hot(y, num_classes, dtype=probs.dtype)
    return jnp.sqrt((err * err).sum(-1) + 1e-12).mean()


# ---------------------------------------------------------------------------
# wire: block-scaled stochastic quantization (the published wire format)
# ---------------------------------------------------------------------------

def _block_uniform(seed, block, shape):
    """U[0,1) per element: a uint32 hash of (seed, block, row, lane)."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    h = (seed.astype(jnp.uint32) * jnp.uint32(2654435761)
         + jnp.uint32(block) * jnp.uint32(976686449)
         + r * jnp.uint32(1664525) + c * jnp.uint32(22695477))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))


def quantize(x, seed, bits):
    """Dequantized value of the b-bit wire payload of `x` (any shape):
    blocks of 256x128 elements, one scale max|x|/qmax per block,
    stochastic rounding floor(x/scale + u), clipped to +-qmax."""
    qmax = {8: 127.0, 4: 7.0}[bits]
    flat = x.reshape(-1).astype(jnp.float32)
    chunk = BLOCK_ROWS * LANES
    n = flat.shape[0]
    nb = -(-n // chunk)
    blocks = jnp.pad(flat, (0, nb * chunk - n)).reshape(nb, BLOCK_ROWS, LANES)
    out = []
    for b in range(nb):
        blk = blocks[b]
        amax = jnp.max(jnp.abs(blk))
        scale = jnp.where(amax > 0.0, amax * jnp.float32(1.0 / qmax), 1.0)
        u = _block_uniform(seed, b, (BLOCK_ROWS, LANES))
        q = jnp.clip(jnp.floor(blk / scale + u), -qmax, qmax)
        out.append(q * scale)
    return jnp.stack(out).reshape(-1)[:n].reshape(x.shape)


def _leaf_seed(key, i):
    return jax.random.randint(jax.random.fold_in(key, i), (), 0,
                              jnp.iinfo(jnp.int32).max)


def compress_ef(delta, residual, key, bits):
    """One worker's uplink with error feedback: the decoded payload of
    delta + residual, and the new residual (what the payload dropped)."""
    leaves, treedef = jax.tree.flatten(delta)
    res = jax.tree.leaves(residual)
    wire, new_res = [], []
    for i, (d, r) in enumerate(zip(leaves, res)):
        acc = d.astype(jnp.float32) + r
        w = quantize(acc, _leaf_seed(key, i), bits)
        wire.append(w)
        new_res.append(acc - w)
    return (jax.tree.unflatten(treedef, wire),
            jax.tree.unflatten(treedef, new_res))


BITS = {"int4": 4, "int8": 8}


# ---------------------------------------------------------------------------
# the round
# ---------------------------------------------------------------------------

def _tmap(f, *t):
    return jax.tree.map(f, *t)


@functools.partial(jax.jit, static_argnames=("L",))
def _score(params, gx, gy, L):
    return jax.vmap(lambda p: rmse(p, gx, gy, L))(params)


@functools.partial(jax.jit, static_argnames=("L",))
def _score_one(params, gx, gy, L):
    return rmse(params, gx, gy, L)


@functools.partial(jax.jit, static_argnames=("L", "E", "bs", "clip"))
def _local(w_in, v_in, best, gbest, x, y, keys, c0, c1, c2, lr, *, L, E, bs,
           clip):
    """Every worker's E epochs of minibatch SGD on its local data, then
    the Eq.-8 velocity (clipped) and the displaced model."""
    grad = jax.grad(lambda p, xb, yb: xent(p, xb, yb, L))

    def one(w0, v, wl, xi, yi, k, c0i, c1i, c2i):
        dt = xi.dtype
        n = xi.shape[0]
        steps = n // bs

        def epoch(p, ek):
            perm = jax.random.permutation(ek, n)[: steps * bs]
            xb = xi[perm].reshape((steps, bs) + xi.shape[1:])
            yb = yi[perm].reshape((steps, bs))

            def step(p, b):
                g = grad(p, *b)
                return _tmap(lambda w, gg: (w - lr * gg).astype(dt), p, g), None
            return jax.lax.scan(step, p, (xb, yb))[0], None

        trained = jax.lax.scan(epoch, w0, jax.random.split(k, E))[0]

        def vel(w, vv, l, gb, tr):
            vn = c0i * vv + c1i * (l - w) + c2i * (gb - w) + (tr - w)
            if clip > 0.0:
                vn = jnp.clip(vn, -clip, clip)
            return vn.astype(dt)
        v_next = _tmap(vel, w0, v, wl, gbest, trained)
        return _tmap(lambda w, vv: (w + vv).astype(dt), w0, v_next), v_next

    return jax.vmap(one)(w_in, v_in, best, x, y, keys, c0, c1, c2)


@functools.partial(jax.jit, static_argnames=("bits",))
def _uplink(delta, residual, keys, bits):
    return jax.vmap(lambda d, r, k: compress_ef(d, r, k, bits))(
        delta, residual, keys)


@functools.partial(jax.jit, static_argnames=("bits",))
def _downlink(delta, residual, key, bits):
    return compress_ef(delta, residual, key, bits)


def init(cfg: dict, spec: dict, seed: int, feed: dict) -> dict:
    """Round-1 state from the seed: every worker at one common init."""
    h, w, ch = cfg["model"]["image"]
    params = init_params(jax.random.PRNGKey(seed + 1),
                         cfg["model"]["width_mult"], ch,
                         cfg["model"]["num_classes"], h, w)
    C = spec["data"]["num_workers"]
    stack = lambda t: _tmap(lambda x: jnp.broadcast_to(x, (C,) + x.shape), t)
    zeros = lambda t: _tmap(lambda x: jnp.zeros(x.shape, jnp.float32), t)
    inf = jnp.float32(jnp.inf)
    return {"params": stack(params), "velocity": zeros(stack(params)),
            "best_params": stack(params), "best_loss": jnp.full((C,), inf),
            "global": params, "gbest": params, "gbest_loss": inf,
            "prev_theta_mean": inf, "round_idx": jnp.int32(0),
            "eta": jnp.asarray(feed["eta"]),
            "residual": zeros(stack(params)), "ps_residual": zeros(params)}


def run_round(view: dict, key, feed: dict, cfg: dict, spec: dict, *,
              dtype: str = "float32", fault: str | None = None,
              prog: dict | None = None, band: float = 0.0) -> dict:
    """One round from `view` with the key the program's step was given.
    Returns the readings the check compares (host arrays)."""
    prec = "highest" if dtype == "float32" else "default"
    with jax.default_matmul_precision(prec):
        return _round(view, key, feed, cfg, spec, jnp.dtype(dtype), fault,
                      prog, band)


def _round(view, key, feed, cfg, spec, dt, fault, prog, band):
    d, a, comm = spec["data"], spec["algo"], spec["comm"]
    hp = a["hp"]
    L = cfg["model"]["num_classes"]
    C = d["num_workers"]
    cast = lambda t: _tmap(lambda x: x.astype(dt), t)
    log = rules.new_log()
    get = lambda k: None if prog is None else prog[k]

    x, y = jnp.asarray(feed["x"], dt), jnp.asarray(feed["y"])
    gx, gy = jnp.asarray(feed["gx"], dt), jnp.asarray(feed["gy"])
    if fault == "half_batch":
        x, y = x[:, : x.shape[1] // 2], y[:, : y.shape[1] // 2]
    w_in = cast(view["params"])
    v_in = cast(view["velocity"])
    best_in = cast(view["best_params"])
    gbest = cast(view["gbest"])
    g_in = cast(view["global"])

    # the runner's step: key -> (next key, round key); the round's split
    _, rkey = jax.random.split(key)
    ckey, tkey, _bkey, qkey, _wkey = jax.random.split(rkey, 5)

    def coeffs(k):
        k0, k1, k2 = jax.random.split(k, 3)
        return (jax.random.uniform(k0, ()), jax.random.normal(k1, ()),
                jax.random.normal(k2, ()))
    c0, c1, c2 = (c.astype(dt) for c in jax.vmap(coeffs)(
        jax.random.split(ckey, C)))
    t = int(view["round_idx"])
    lr = jnp.asarray(hp["learning_rate"] * hp["lr_decay"]
                     ** (t // hp["lr_decay_every"]), dt)

    # Eq. 9 on the pre-update models (F_{i,t} on D_g)
    pre = _score(w_in, gx, gy, L)
    improved = rules.decide(np.asarray(pre, np.float32),
                            np.asarray(view["best_loss"], np.float32),
                            get("local_improved"), get("pre_losses"), band,
                            log)
    imp = jnp.asarray(improved)
    best = _tmap(lambda n, o: jnp.where(
        imp.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), w_in, best_in)

    # LocalUpdate: E epochs of minibatch SGD, then the Eq.-8 displacement
    w_out, v_out = _local(w_in, v_in, best, gbest, x, y,
                          jax.random.split(tkey, C), c0, c1, c2, lr,
                          L=L, E=a["local_epochs"],
                          bs=min(a["batch_size"], x.shape[1]),
                          clip=float(hp["velocity_clip"]))
    losses = _score(w_out, gx, gy, L)

    # ScoreSelect: Eq. 5 scores, Eq. 6 threshold (with >= 1 selected)
    tau = a["tau"]
    eta = np.asarray(view["eta"], np.float32)
    theta = tau * np.asarray(losses, np.float32) + (1 - tau) * eta
    mask = rules.select(theta, float(view["prev_theta_mean"]), get("mask"),
                        get("theta"), band, log,
                        invert=fault == "invert_select")
    m = jnp.asarray(mask, jnp.float32)

    # Uplink -> Aggregate -> Downlink
    delta = _tmap(lambda n, o: n - o, w_out, w_in)
    if fault == "flip_upload":
        first = int(np.argmax(mask))
        sign = jnp.where(jnp.arange(C) == first, -1.0, 1.0).astype(dt)
        delta = _tmap(lambda dd: dd * sign.reshape((-1,) + (1,) * (dd.ndim - 1)),
                      delta)
    res_in = _tmap(lambda r: jnp.asarray(r, jnp.float32), view["residual"])
    if fault == "no_feedback":
        res_in = _tmap(jnp.zeros_like, res_in)
    bits = BITS.get(comm["compressor"])
    if bits is None:
        wire = _tmap(lambda dd, r: dd.astype(jnp.float32) + r, delta, res_in)
        new_res = _tmap(jnp.zeros_like, res_in)
    else:
        wire, new_res = _uplink(delta, res_in, jax.random.split(qkey, C),
                                bits)
    if fault == "no_feedback":
        new_res = res_in
    # error feedback advances for the selected workers only
    res_out = _tmap(lambda n, o: jnp.where(
        m.reshape((-1,) + (1,) * (n.ndim - 1)) > 0, n, o), new_res, res_in)
    denom = jnp.maximum(m.sum(), 1.0)
    agg = _tmap(lambda g, w: (g.astype(jnp.float32)
                              + (m.reshape((-1,) + (1,) * (w.ndim - 1)) * w
                                 ).sum(0) / denom).astype(dt), g_in, wire)
    dbits = BITS.get(comm["downlink_compressor"])
    ps_in = _tmap(lambda r: jnp.asarray(r, jnp.float32), view["ps_residual"])
    if dbits is None:
        g_out, ps_out = agg, ps_in
    else:
        ddelta = _tmap(lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                       agg, g_in)
        dwire, ps_out = _downlink(ddelta, ps_in,
                                  jax.random.fold_in(qkey, DOWNLINK_SALT),
                                  dbits)
        g_out = _tmap(lambda g, w: (g.astype(jnp.float32) + w).astype(dt),
                      g_in, dwire)
    if fault == "no_exchange":
        g_out = g_in

    # BestTracking: Eq. 10 on the broadcast model
    gl = float(_score_one(g_out, gx, gy, L))
    g_imp = bool(rules.decide(gl, float(view["gbest_loss"]),
                              get("global_improved"), get("global_loss"),
                              band, log))
    gbest_out = g_out if g_imp else gbest

    norm = lambda t: [float(jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)))))
                      for l in jax.tree.leaves(t)]
    diff = lambda a, b: norm(_tmap(lambda n, o: n.astype(jnp.float32)
                                   - o.astype(jnp.float32), a, b))
    return {"losses": np.asarray(losses, np.float32),
            "theta": theta.astype(np.float32),
            "pre_losses": np.asarray(pre, np.float32),
            "global_loss": gl,
            "mask": mask.astype(np.float32),
            "local_improved": np.asarray(improved, bool),
            "global_improved": g_imp,
            "velocity_norms": norm(v_out),
            "changes": {"global": diff(g_out, g_in),
                        "best": diff(best, best_in),
                        "gbest": diff(gbest_out, gbest),
                        "residual": diff(res_out, res_in if fault != "no_feedback"
                                         else view["residual"]),
                        "ps_residual": diff(ps_out, ps_in)},
            **log}
