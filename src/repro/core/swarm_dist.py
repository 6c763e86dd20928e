"""M-DSL as a mesh-distributed train step (production integration).

The paper's C edge workers map onto the mesh as data-parallel groups
(DESIGN.md §3): the swarm state carries a leading *spatial worker* dim W
sharded over `worker_axes`; each worker's replica is sharded over the
remaining axes (TP over "model", FSDP over "data" in fsdp mode). One
jitted `train_step` is one communication round, built as a thin
configuration of `core/rounds.py`'s stage pipeline: this module supplies
only the LocalUpdate stage (local SGD steps + Eq. 8 PSO displacement,
with `spmd_axis_name` vmap over W); ScoreSelect, the Eq.-7 wire
(compression, channel, robust aggregation, compressed downlink), and
byte accounting are the shared stages — the masked delta-mean lowers to
ONE all-reduce over worker_axes exactly as before.

vmap over the worker dim uses `spmd_axis_name=worker_axes` so internal
sharding constraints stay consistent with the worker sharding. Workers
on no mesh axis share the devices and run one after another
(`lax.map`): the step then holds one worker's activations, and
per-worker grouped expert products stay unbatched (the TPU lowering of
`ragged_dot` takes no batch dimension). With W == 1 (fsdp mode: the
time-multiplexed swarm) the local-update vmap is skipped and
`temporal_workers` rounds can be scanned by the caller.

`fedavg_train_step` is the same pipeline with the all-ones selection
stage (algorithm="fedavg") and plain-SGD local deltas — the baseline
rides the identical wire, so robust aggregation and downlink
compression apply to it too.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comm import compress as comm_compress
from repro.comm import channel as comm_channel
from repro.comm import phy as comm_phy
from repro.comm import straggler as comm_straggler
from repro.comm.budget import CommConfig
from repro.core import pso, rounds
from repro.core.pso import PsoHyperParams
from repro.core.rounds import RoundTelemetry

Array = jax.Array
PyTree = Any

# pre-refactor alias: the mesh path's info is the unified telemetry
RoundInfo = RoundTelemetry


class DistSwarmConfig(NamedTuple):
    worker_axes: tuple[str, ...]    # () => single spatial worker (fsdp mode)
    num_spatial: int                # W
    local_steps: int = 1
    tau: float = 0.9
    hp: PsoHyperParams = PsoHyperParams(learning_rate=3e-3,
                                        velocity_clip=1.0)
    # grad-accumulation chunks per local step: caps per-device activation
    # memory at batch/microbatches (EXPERIMENTS.md §Perf iteration 2)
    microbatches: int = 1
    comm: CommConfig = CommConfig()  # wire: compression/channel/aggregation


class DistSwarmState(NamedTuple):
    """All worker leaves stacked over W; global leaves unstacked."""
    params: PyTree            # (W, ...) worker models
    velocity: PyTree          # (W, ...)
    best_params: PyTree       # (W, ...) w^l (Eq. 9)
    best_loss: Array          # (W,)
    global_params: PyTree     # w_t (replicated over worker axes)
    gbest_params: PyTree      # w^g-bar (Eq. 10)
    gbest_loss: Array         # ()
    prev_theta_mean: Array    # () Eq. 6 threshold
    eta: Array                # (W,) non-iid degrees
    round_idx: Array          # ()
    residual: PyTree          # (W, ...) uplink error-feedback state
    ps_residual: PyTree       # PS-side downlink error-feedback state
    phy: comm_phy.PhyState    # (W,) per-worker channel state (comm.phy)
    # (W, ...) parked late deltas + staleness ages (comm.straggler);
    # None unless comm.round_deadline_s is set
    buffer: Any = None


def init_state(global_params: PyTree, cfg: DistSwarmConfig,
               eta: Optional[Array] = None) -> DistSwarmState:
    W = cfg.num_spatial
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), t)
    zeros = jax.tree.map(jnp.zeros_like, global_params)
    return DistSwarmState(
        params=stack(global_params),
        velocity=stack(zeros),
        best_params=stack(global_params),
        best_loss=jnp.full((W,), jnp.inf, jnp.float32),
        global_params=global_params,
        gbest_params=global_params,
        gbest_loss=jnp.asarray(jnp.inf, jnp.float32),
        prev_theta_mean=jnp.asarray(jnp.inf, jnp.float32),
        eta=jnp.zeros((W,), jnp.float32) if eta is None else eta,
        round_idx=jnp.zeros((), jnp.int32),
        residual=stack(comm_compress.init_residual(global_params)),
        ps_residual=rounds.init_ps_residual(global_params),
        phy=comm_phy.init_state(cfg.comm, W),
        buffer=comm_straggler.init_buffer(
            cfg.comm, stack(comm_compress.init_residual(global_params))),
    )


def _spmd_axis_name(cfg: DistSwarmConfig):
    """vmap spmd_axis_name for the worker dim: None when the worker dim is
    not mesh-sharded (pure-CPU tests / temporal-only swarm with W>1)."""
    if len(cfg.worker_axes) == 0:
        return None
    if len(cfg.worker_axes) == 1:
        return cfg.worker_axes[0]
    return cfg.worker_axes


def _over_workers(fn: Callable, cfg: DistSwarmConfig,
                  in_axes: tuple) -> Callable:
    """`fn` over the leading worker dim of the arguments whose `in_axes`
    entry is 0 (None: shared by every worker): vmapped when the workers
    sit on mesh axes, else one worker at a time."""
    if cfg.worker_axes:
        return jax.vmap(fn, in_axes=in_axes,
                        spmd_axis_name=_spmd_axis_name(cfg))

    def one_by_one(*args):
        def one(xs):
            it = iter(xs)
            return fn(*(next(it) if ax == 0 else a
                        for a, ax in zip(args, in_axes)))
        return jax.lax.map(one, [a for a, ax in zip(args, in_axes)
                                 if ax == 0])
    return one_by_one


def _pipeline(cfg: DistSwarmConfig, algorithm: str,
              params_template: PyTree = None) -> rounds.RoundPipeline:
    return rounds.RoundPipeline(
        algorithm=algorithm, comm=cfg.comm, num_workers=cfg.num_spatial,
        tau=cfg.tau, axis_name=_spmd_axis_name(cfg),
        n_params=(rounds.count_params(params_template)
                  if params_template is not None else 0))


def build_train_step(loss_fn: Callable[[PyTree, dict], Array],
                     cfg: DistSwarmConfig
                     ) -> Callable[..., tuple[DistSwarmState, RoundInfo]]:
    """loss_fn(params, batch) -> scalar. Returns
    train_step(state, batch, eval_batch, key) where every leaf of `batch`
    has a leading worker dim W."""

    W = cfg.num_spatial
    grad_fn = jax.value_and_grad(loss_fn)

    def local_round(params, velocity, best_params, gbest_params, batch,
                    coeffs=None, lr=None):
        """LocalUpdate: local SGD steps + Eq. 8 PSO displacement."""
        w0 = params

        def sgd(p, _):
            g = rounds.accumulated_grad(grad_fn, p, batch, cfg.microbatches)
            return pso.sgd_step(p, g, lr), None

        trained, _ = jax.lax.scan(sgd, w0, None, length=cfg.local_steps)
        sgd_delta = jax.tree.map(lambda a, b: a - b, trained, w0)

        def leaf(w, v, wl, wg, d):
            v_new = (coeffs.c0 * v + coeffs.c1 * (wl - w)
                     + coeffs.c2 * (wg - w) + d)
            if cfg.hp.velocity_clip > 0:
                v_new = jnp.clip(v_new, -cfg.hp.velocity_clip,
                                 cfg.hp.velocity_clip)
            return v_new.astype(w.dtype)
        v_next = jax.tree.map(leaf, w0, velocity, best_params, gbest_params,
                              sgd_delta)
        p_next = jax.tree.map(jnp.add, w0, v_next)
        return p_next, v_next

    def train_step(state: DistSwarmState, batch: PyTree, eval_batch: PyTree,
                   key: Array) -> tuple[DistSwarmState, RoundInfo]:
        pipe = _pipeline(cfg, "mdsl", state.global_params)
        # per-worker coefficient draws (see core/mdsl.py)
        ckey, bkey, qkey, wkey = jax.random.split(key, 4)
        coeffs = jax.vmap(pso.sample_coefficients)(jax.random.split(ckey, W))
        lr = pso.decayed_lr(cfg.hp, state.round_idx)

        run_local = functools.partial(local_round, lr=lr)
        eval_one = lambda p: loss_fn(p, eval_batch)
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        ex = lambda t: jax.tree.map(lambda x: x[None], t)
        with rounds.stage_span("LocalUpdate"):
            if W == 1:
                p1, v1 = run_local(sq(state.params), sq(state.velocity),
                                   sq(state.best_params),
                                   state.gbest_params,
                                   jax.tree.map(lambda x: x[0], batch),
                                   coeffs=sq(coeffs))
                new_params, new_vel = ex(p1), ex(v1)
            else:
                per_worker = _over_workers(run_local, cfg,
                                           (0, 0, 0, None, 0, 0))
                new_params, new_vel = per_worker(state.params, state.velocity,
                                                 state.best_params,
                                                 state.gbest_params, batch,
                                                 coeffs)

            # Byzantine workers' local updates are adversarial
            # (comm/channel): corruption lands in their params so Eq. 6
            # can reject them.
            new_params = comm_channel.corrupt_local_updates(
                cfg.comm, state.params, new_params, bkey)
            if W == 1:
                losses = eval_one(sq(new_params))[None]
            else:
                losses = _over_workers(eval_one, cfg, (0,))(new_params)

        # --- ScoreSelect (Eqs. 5-6) ---------------------------------------
        theta, mask, theta_mean = pipe.select(losses, state.eta,
                                              state.prev_theta_mean)

        # --- Uplink -> Aggregate -> Downlink (Eq. 7 through the wire):
        # one all-reduce over worker axes; default CommConfig reduces to
        # the seed's masked mean and a dense broadcast. ---
        delta = jax.tree.map(lambda a, b: a - b, new_params, state.params)
        out = pipe.wire(delta=delta, theta=theta, mask=mask,
                        global_params=state.global_params,
                        residual=state.residual,
                        ps_residual=state.ps_residual,
                        qkey=qkey, wkey=wkey, phy=state.phy,
                        buffer=state.buffer, round_idx=state.round_idx)
        global_loss = eval_one(out.global_params)

        # --- BestTracking (Eqs. 9-10) -------------------------------------
        with rounds.stage_span("BestTracking"):
            best_params, best_loss = rounds.track_local_best(
                state.best_params, state.best_loss, new_params, losses)
            gbest_params, gbest_loss = rounds.track_global_best(
                state.gbest_params, state.gbest_loss, out.global_params,
                global_loss)

        next_state = DistSwarmState(
            params=new_params, velocity=new_vel, best_params=best_params,
            best_loss=best_loss, global_params=out.global_params,
            gbest_params=gbest_params, gbest_loss=gbest_loss,
            prev_theta_mean=theta_mean, eta=state.eta,
            round_idx=state.round_idx + 1, residual=out.residual,
            ps_residual=out.ps_residual, phy=out.phy, buffer=out.buffer)
        return next_state, pipe.telemetry(losses=losses, theta=theta,
                                          mask=mask,
                                          global_loss=global_loss,
                                          outcome=out)

    return train_step


def fedavg_train_step(loss_fn, cfg: DistSwarmConfig):
    """Baseline: plain data-parallel FedAvg round (all workers, SGD only)
    — the same pipeline with the all-ones selection stage. Used for
    paper-faithful comparisons at mesh scale and as the roofline
    reference for the selection overhead."""
    grad_fn = jax.value_and_grad(loss_fn)
    W = cfg.num_spatial

    def local(params, batch, lr):
        def sgd(p, _):
            g = rounds.accumulated_grad(grad_fn, p, batch, cfg.microbatches)
            return pso.sgd_step(p, g, lr), None
        trained, _ = jax.lax.scan(sgd, params, None, length=cfg.local_steps)
        return jax.tree.map(lambda a, b: a - b, trained, params)

    def train_step(state: DistSwarmState, batch, eval_batch, key):
        pipe = _pipeline(cfg, "fedavg", state.global_params)
        bkey, qkey, wkey = jax.random.split(key, 3)
        lr = pso.decayed_lr(cfg.hp, state.round_idx)
        with rounds.stage_span("LocalUpdate"):
            if W == 1:
                delta = local(state.global_params,
                              jax.tree.map(lambda x: x[0], batch), lr)
                deltas = jax.tree.map(lambda x: x[None], delta)
            else:
                deltas = _over_workers(
                    lambda b: local(state.global_params, b, lr), cfg,
                    (0,))(batch)
            # FedAvg rides the same wire: byzantine deltas, compression
            # with error feedback, channel — but every worker uploads
            # (mask = 1).
            zeros = jax.tree.map(jnp.zeros_like, deltas)
            deltas = comm_channel.corrupt_local_updates(cfg.comm, zeros,
                                                        deltas, bkey)
            # real per-worker scores: F_i at w_t + delta_i on the eval
            # batch
            worker_params = jax.tree.map(lambda g, d: g[None] + d,
                                         state.global_params, deltas)
            eval_one = lambda p: loss_fn(p, eval_batch)
            if W == 1:
                losses = eval_one(jax.tree.map(lambda x: x[0],
                                               worker_params))[None]
            else:
                losses = _over_workers(eval_one, cfg, (0,))(worker_params)
        theta, mask, _ = pipe.select(losses, state.eta,
                                     state.prev_theta_mean)

        out = pipe.wire(delta=deltas, theta=theta, mask=mask,
                        global_params=state.global_params,
                        residual=state.residual,
                        ps_residual=state.ps_residual,
                        qkey=qkey, wkey=wkey, phy=state.phy,
                        buffer=state.buffer, round_idx=state.round_idx)
        global_loss = loss_fn(out.global_params, eval_batch)
        next_state = state._replace(global_params=out.global_params,
                                    round_idx=state.round_idx + 1,
                                    residual=out.residual,
                                    ps_residual=out.ps_residual,
                                    phy=out.phy, buffer=out.buffer)
        return next_state, pipe.telemetry(losses=losses, theta=theta,
                                          mask=mask,
                                          global_loss=global_loss,
                                          outcome=out)

    return train_step
