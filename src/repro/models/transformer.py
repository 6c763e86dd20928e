"""Composable transformer model family (all 10 assigned architectures).

A model is built from an `ArchConfig`: the depth-wise `block_pattern`
(attn | swa | mla | rglru | mlstm | slstm) is cycled over `num_layers`;
attention-family blocks get a channel mixer (gated MLP, or MoE when
`num_experts > 0`); xLSTM blocks embed their own mixers (d_ff = 0). The
first `first_k_dense` layers (DeepSeek-V3) take a dense MLP of width
`dense_d_ff` in place of the experts and run unrolled before the scan.
MLA sub-blocks run under the named scope `MLA` and MoE mixers under
`MoE`, so a profile can attribute device time to them.
Optional extras per config: cross-attention decoder (audio enc-dec),
token+prefix-embedding inputs (VLM), encoder stack.

Layers are *scanned*: the pattern repeats `num_layers // P` times, so
params/caches carry a leading repetition dim and the HLO contains one
instance of the pattern body regardless of depth (MaxText-style; critical
for 95-layer AOT compiles on one CPU core). Remainder layers (L % P) are
unrolled.

Public API (used by launch/, tests, benchmarks):
    model = Transformer(cfg)
    params = model.init(key)                       # or jax.eval_shape
    logits, aux = model.forward(params, batch)     # train/teacher-forcing
    loss = model.loss(params, batch)
    cache = model.init_cache(batch_size, cache_len)
    logits, cache = model.prefill(params, batch, cache)
    logits, cache = model.decode_step(params, tokens, cache, memory=None)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, SWA, MLA, RGLRU, MLSTM, SLSTM, ArchConfig
from repro.models import layers, moe, recurrent
from repro.models.layers import cdtype
from repro.sharding import shard

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# single layer = temporal block (+ cross-attn) (+ channel mixer)
# ---------------------------------------------------------------------------

def _mixer_kind(cfg: ArchConfig, block_kind: str, dense: bool = False) -> str:
    if block_kind in (MLSTM, SLSTM):
        return "none"
    if cfg.num_experts and not dense:
        return "moe"
    return "mlp" if cfg.d_ff else "none"


def layer_init(key: Array, cfg: ArchConfig, block_kind: str,
               cross: bool, dense: bool = False) -> PyTree:
    """`dense`: a leading dense layer (first_k_dense) of a MoE model."""
    k1, k2, k3 = jax.random.split(key, 3)
    p: PyTree = {}
    if block_kind in (ATTN, SWA):
        p["temporal"] = layers.attention_init(k1, cfg)
    elif block_kind == MLA:
        p["temporal"] = layers.mla_init(k1, cfg)
    elif block_kind == RGLRU:
        p["temporal"] = recurrent.rglru_init(k1, cfg)
    elif block_kind == MLSTM:
        p["temporal"] = recurrent.mlstm_init(k1, cfg)
    elif block_kind == SLSTM:
        p["temporal"] = recurrent.slstm_init(k1, cfg)
    else:
        raise ValueError(block_kind)
    if cross:
        p["cross"] = layers.attention_init(k2, cfg, cross=True)
    mk = _mixer_kind(cfg, block_kind, dense)
    if mk == "mlp":
        d_ff = (cfg.dense_d_ff or cfg.d_ff) if dense else cfg.d_ff
        p["mlp"] = layers.mlp_init(k3, cfg.d_model, d_ff, cfg)
    elif mk == "moe" and cfg.router == "sigmoid":
        p["moe"] = moe.deepseek_moe_init(k3, cfg)
    elif mk == "moe":
        p["moe"] = moe.moe_init(k3, cfg)
    return p


def layer_apply(p: PyTree, x: Array, cfg: ArchConfig, block_kind: str, *,
                mode: str, cache: Optional[PyTree],
                memory_kv: Optional[tuple] = None,
                positions: Optional[Array] = None, dense: bool = False
                ) -> tuple[Array, Optional[PyTree], Array]:
    """Returns (x_out, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    tcache = None if cache is None else cache.get("temporal")

    if block_kind in (ATTN, SWA):
        window = cfg.window_size if block_kind == SWA else 0
        y, nc = layers.attention_apply(
            p["temporal"], x, cfg, mode=mode, layer_cache=tcache,
            window=window, positions=positions)
    elif block_kind == MLA:
        if mode != "train" or cache is not None:
            raise NotImplementedError("MLA runs whole sequences only: "
                                      "no latent KV cache for decoding")
        with jax.named_scope("MLA"):
            y, nc = layers.mla_apply(p["temporal"], x, cfg), None
    elif block_kind == RGLRU:
        y, nc = recurrent.rglru_apply(p["temporal"], x, cfg, mode=mode,
                                      layer_cache=tcache)
    elif block_kind == MLSTM:
        y, nc = recurrent.mlstm_block_apply(p["temporal"], x, cfg, mode=mode,
                                            layer_cache=tcache)
    elif block_kind == SLSTM:
        y, nc = recurrent.slstm_apply(p["temporal"], x, cfg, mode=mode,
                                      layer_cache=tcache)
    else:
        raise ValueError(block_kind)
    x = x + y
    if nc is not None:
        new_cache["temporal"] = nc

    if "cross" in p and memory_kv is not None:
        y, _ = layers.attention_apply(p["cross"], x, cfg, mode=mode,
                                      memory_kv=memory_kv)
        x = x + y

    mk = _mixer_kind(cfg, block_kind, dense)
    if mk == "mlp":
        x = x + layers.mlp_apply(p["mlp"], x, cfg)
    elif mk == "moe":
        with jax.named_scope("MoE"):
            if cfg.router == "sigmoid":
                y, aux = moe.deepseek_moe_apply(p["moe"], x, cfg)
            else:
                y, aux = moe.moe_apply(p["moe"], x, cfg)
        x = x + y
    return x, (new_cache if new_cache else None), aux


def init_layer_cache(cfg: ArchConfig, block_kind: str, batch: int,
                     cache_len: int, dtype) -> PyTree:
    if block_kind == ATTN:
        return {"temporal": layers.init_attention_cache(
            cfg, batch, cache_len, 0, dtype)}
    if block_kind == SWA:
        return {"temporal": layers.init_attention_cache(
            cfg, batch, cache_len, cfg.window_size, dtype)}
    if block_kind == RGLRU:
        return {"temporal": recurrent.init_rglru_cache(cfg, batch, dtype)}
    if block_kind == MLSTM:
        return {"temporal": recurrent.init_mlstm_cache(cfg, batch)}
    if block_kind == SLSTM:
        return {"temporal": recurrent.init_slstm_cache(cfg, batch)}
    raise ValueError(block_kind)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

class Transformer:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        P = len(cfg.block_pattern)
        self.n_dense = cfg.first_k_dense
        self.n_rep = (cfg.num_layers - self.n_dense) // P
        self.n_rem = (cfg.num_layers - self.n_dense) % P
        self.pattern = cfg.block_pattern

    # -- init ---------------------------------------------------------------
    def init(self, key: Array) -> PyTree:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        params: PyTree = {
            "embed": layers.embedding_init(keys[0], cfg.vocab_size,
                                           cfg.d_model, cdtype(cfg)),
            "final_norm": layers.rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["head"] = {"table": layers.dense_init(
                keys[4], (cfg.vocab_size, cfg.d_model), in_axis=1,
                dtype=cdtype(cfg))}
        cross = cfg.cross_attention
        for i in range(self.n_dense):
            params[f"dense{i}"] = layer_init(
                jax.random.fold_in(keys[5], i), cfg, self.pattern[0], cross,
                dense=True)

        def group_init(gkey):
            ks = jax.random.split(gkey, len(self.pattern))
            return {f"b{j}": layer_init(ks[j], cfg, kind, cross)
                    for j, kind in enumerate(self.pattern)}

        if self.n_rep:
            params["groups"] = jax.vmap(group_init)(
                jax.random.split(keys[1], self.n_rep))
        for r in range(self.n_rem):
            kind = self.pattern[r]
            params[f"rem{r}"] = layer_init(
                jax.random.fold_in(keys[2], r), cfg, kind, cross)

        if cfg.encoder_layers:
            enc_cfg = cfg
            def enc_layer_init(k):
                return layer_init(k, enc_cfg, ATTN, cross=False)
            params["encoder"] = {
                "layers": jax.vmap(enc_layer_init)(
                    jax.random.split(keys[3], cfg.encoder_layers)),
                "final_norm": layers.rmsnorm_init(cfg.d_model),
            }
        return params

    # -- embedding / inputs ---------------------------------------------------
    def _embed_inputs(self, params: PyTree, batch: dict) -> Array:
        cfg = self.cfg
        if cfg.input_mode == "embeddings":
            x = batch["embeddings"].astype(cdtype(cfg))
        elif cfg.input_mode == "tokens+prefix":
            tok = layers.embed(params["embed"], batch["tokens"])
            prefix = batch["prefix"].astype(tok.dtype)
            prefix = shard(prefix, ("batch", "seq", "embed"))
            x = jnp.concatenate([prefix, tok], axis=1)
        else:
            x = layers.embed(params["embed"], batch["tokens"])
        return shard(x, ("batch", "seq", "embed"))

    # -- encoder --------------------------------------------------------------
    def encode(self, params: PyTree, frames: Array) -> Array:
        """frames: (B, M, d) precomputed frontend embeddings -> memory."""
        cfg = self.cfg
        x = frames.astype(cdtype(cfg))

        def body(x, lp):
            y, _, _ = layer_apply(lp, x, cfg, ATTN, mode="encode", cache=None)
            return y, None

        if cfg.remat:  # same per-layer checkpointing as the decoder stack
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
        return layers.rmsnorm(params["encoder"]["final_norm"], x,
                              cfg.norm_eps)

    def _memory_kv(self, params_attn: PyTree, memory: Array
                   ) -> tuple[Array, Array]:
        """Precompute cross-attention K/V from encoder memory."""
        h = layers.rmsnorm(params_attn["norm"], memory, self.cfg.norm_eps)
        k = jnp.einsum("bsd,dhk->bshk", h, params_attn["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, params_attn["wv"])
        return k, v

    # -- full-sequence forward (train / teacher forcing) ----------------------
    def forward(self, params: PyTree, batch: dict,
                mode: str = "train") -> tuple[Array, Array]:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        memory = None
        if cfg.encoder_layers:
            memory = self.encode(params, batch["frames"])

        aux_total = jnp.zeros((), jnp.float32)

        def dense_layer(x, lp):
            return layer_apply(lp, x, cfg, self.pattern[0], mode=mode,
                               cache=None, dense=True)[0]
        if cfg.remat:
            dense_layer = jax.checkpoint(
                dense_layer, policy=jax.checkpoint_policies.nothing_saveable)
        for i in range(self.n_dense):
            x = dense_layer(x, params[f"dense{i}"])

        def apply_group(x, gparams, aux):
            for j, kind in enumerate(self.pattern):
                mkv = None
                if "cross" in gparams[f"b{j}"] and memory is not None:
                    mkv = self._memory_kv(gparams[f"b{j}"]["cross"], memory)
                x, _, a = layer_apply(gparams[f"b{j}"], x, cfg, kind,
                                      mode=mode, cache=None, memory_kv=mkv)
                aux = aux + a
            return x, aux

        if self.n_rep:
            def body(carry, gparams):
                x, aux = carry
                x, aux = apply_group(x, gparams, aux)
                # sequence-parallel residual boundary: the remat-saved
                # carry stack shards its seq dim over "model" (rules map
                # residual_seq -> model at train), cutting the dominant
                # train-time buffer by the TP degree; GSPMD re-gathers
                # K/V inside the layer where full seq is needed
                x = shard(x, ("batch", "residual_seq", "embed"))
                return (x, aux), None
            if cfg.remat:
                # per-group activation checkpointing: backward recomputes
                # the group from its (B,S,D) input; without this the scan
                # stacks every attention/MLP intermediate for the bwd pass
                # (hundreds of GiB/device at train_4k - see EXPERIMENTS.md
                # §Perf iteration 1)
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                             params["groups"])
        # remainder layers (unrolled, single layer each)
        for r in range(self.n_rem):
            kind = self.pattern[r]
            mkv = None
            if "cross" in params[f"rem{r}"] and memory is not None:
                mkv = self._memory_kv(params[f"rem{r}"]["cross"], memory)
            x, _, a = layer_apply(params[f"rem{r}"], x, cfg, kind, mode=mode,
                                  cache=None, memory_kv=mkv)
            aux_total = aux_total + a

        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = layers.unembed(params.get("head", params["embed"]), x)
        return logits, aux_total

    # -- loss ------------------------------------------------------------------
    def loss(self, params: PyTree, batch: dict) -> Array:
        """Next-token cross-entropy (+ the MoE router's balance loss,
        weighted by cfg.router_aux_weight): position t is scored on
        batch["labels"][:, t + 1], so the labels are the tokens
        themselves, unshifted. Labels: (B, S) with -1 = masked."""
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        if self.cfg.input_mode == "tokens+prefix":
            logits = logits[:, self.cfg.prefix_len:]
        logits = logits[:, :-1]
        targets = labels[:, 1:]
        mask = targets >= 0
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(lp, jnp.maximum(targets, 0)[..., None],
                                 axis=-1)[..., 0]
        ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)
        return ce + self.cfg.router_aux_weight * aux

    # -- caches ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int,
                   memory: Optional[Array] = None,
                   params: Optional[PyTree] = None) -> PyTree:
        cfg = self.cfg
        dt = cdtype(cfg)
        cache: PyTree = {}

        def one(kind):
            return init_layer_cache(cfg, kind, batch, cache_len, dt)

        if self.n_rep:
            def group_cache(_):
                return {f"b{j}": one(kind)
                        for j, kind in enumerate(self.pattern)}
            cache["groups"] = jax.vmap(group_cache)(jnp.arange(self.n_rep))
        for r in range(self.n_rem):
            cache[f"rem{r}"] = one(self.pattern[r])

        if cfg.cross_attention and memory is not None and params is not None:
            # precompute cross K/V per decoder layer (prefill-time)
            if self.n_rep:
                cache["cross_kv"] = jax.vmap(
                    lambda gp: {f"b{j}": jnp.stack(self._memory_kv(
                        gp[f"b{j}"]["cross"], memory))
                        for j in range(len(self.pattern))}
                )(params["groups"])
            for r in range(self.n_rem):
                cache[f"cross_kv_rem{r}"] = jnp.stack(self._memory_kv(
                    params[f"rem{r}"]["cross"], memory))
        return cache

    # -- prefill / decode ----------------------------------------------------
    def _run_with_cache(self, params: PyTree, x: Array, cache: PyTree,
                        mode: str) -> tuple[Array, PyTree]:
        cfg = self.cfg

        def apply_one(x, lp, lc, kind, cross_kv):
            mkv = None
            if cross_kv is not None:
                mkv = (cross_kv[0], cross_kv[1])
            return layer_apply(lp, x, cfg, kind, mode=mode, cache=lc,
                               memory_kv=mkv)

        new_cache: PyTree = {}
        if self.n_rep:
            has_cross = "cross_kv" in cache

            def body(x, xs):
                gp, gc, ckv = xs
                ncs = {}
                for j, kind in enumerate(self.pattern):
                    mkv = ckv[f"b{j}"] if ckv is not None else None
                    x, nc, _ = apply_one(x, gp[f"b{j}"], gc[f"b{j}"], kind,
                                         mkv)
                    ncs[f"b{j}"] = nc
                return x, ncs

            xs = (params["groups"], cache["groups"],
                  cache["cross_kv"] if has_cross else None)
            if has_cross:
                x, gcache = jax.lax.scan(body, x, xs)
            else:
                def body2(x, xs2):
                    gp, gc = xs2
                    return body(x, (gp, gc, None))
                x, gcache = jax.lax.scan(body2, x,
                                         (params["groups"], cache["groups"]))
            new_cache["groups"] = gcache
            if has_cross:
                new_cache["cross_kv"] = cache["cross_kv"]
        for r in range(self.n_rem):
            kind = self.pattern[r]
            ckv = cache.get(f"cross_kv_rem{r}")
            x, nc, _ = apply_one(x, params[f"rem{r}"], cache[f"rem{r}"],
                                 kind, ckv)
            new_cache[f"rem{r}"] = nc
            if ckv is not None:
                new_cache[f"cross_kv_rem{r}"] = ckv

        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, new_cache

    def prefill(self, params: PyTree, batch: dict,
                cache: PyTree) -> tuple[Array, PyTree]:
        """Run the prompt through the model, filling the cache. Returns
        (last-position logits, cache)."""
        x = self._embed_inputs(params, batch)
        x, cache = self._run_with_cache(params, x, cache, "prefill")
        logits = layers.unembed(params.get("head", params["embed"]),
                                x[:, -1:])
        return logits, cache

    def decode_step(self, params: PyTree, tokens: Array,
                    cache: PyTree) -> tuple[Array, PyTree]:
        """tokens: (B, 1). Returns (logits (B,1,V), new cache)."""
        x = layers.embed(params["embed"], tokens)
        x = shard(x, ("batch", "seq", "embed"))
        x, cache = self._run_with_cache(params, x, cache, "decode")
        logits = layers.unembed(params.get("head", params["embed"]), x)
        return logits, cache
