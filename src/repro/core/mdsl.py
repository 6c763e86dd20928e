"""M-DSL communication round and baselines (paper Algorithm 1 + §V-B).

One engine, four algorithms, differing only in (a) the local update rule
and (b) the selection rule:

  fedavg    SGD local epochs, all workers aggregated           [17]
  dsl       PSO-hybrid local update, single best worker        [9]
  multi_dsl PSO-hybrid, multi-worker selection with tau=1
            (score = F only; the paper's ablation in Fig. 3)
  mdsl      PSO-hybrid, multi-worker selection with
            theta = tau*F + (1-tau)*eta  (the contribution)

The round is a configuration of `core/rounds.py`'s stage pipeline:
this module supplies only the LocalUpdate stage (PSO-hybrid local
epochs, vmap'ed over the leading C dim) and the WorkerState-shaped
best tracking; ScoreSelect, Uplink, Aggregate, Downlink, and the byte
accounting are the shared stages in `rounds.RoundPipeline`. The same
pipeline drives the mesh-distributed production trainer
(`core/swarm_dist.py`), where the worker dim is sharded over mesh axes.

Granularity note (DESIGN.md §1): Algorithm 1 applies Eq. 8 once per
communication round while §V-A trains 4 local epochs per round. We
therefore run E epochs of minibatch SGD and treat the accumulated local
progress as Eq. 8's "-alpha grad F" term, adding the PSO velocity /
cognitive / social terms once per round. With E=1 and a single full-batch
step this reduces exactly to Eq. 8. Per-step PSO is available via
`pso_every_step=True` for the convergence unit tests.

F_{i,t} used for bests and selection is evaluated on the shared synthetic
dataset D_g ("workers also have a synthetic global dataset D_g for
function value evaluation", §III-A) so scores are comparable across
workers; the training gradient uses the local D_i.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import channel as comm_channel
from repro.comm import compress as comm_compress
from repro.comm import phy as comm_phy
from repro.comm import straggler as comm_straggler
from repro.comm.budget import CommConfig
from repro.core import pso, rounds, selection
from repro.core.pso import (GlobalBest, PsoCoefficients, PsoHyperParams,
                            WorkerState)
from repro.core.rounds import RoundTelemetry
from repro.core.selection import SelectionState

Array = jax.Array
PyTree = Any
LossFn = Callable[[PyTree, Array, Array], Array]  # (params, x, y) -> scalar

# pre-refactor alias: the paper path's metrics are the unified telemetry
RoundMetrics = RoundTelemetry


class MdslConfig(NamedTuple):
    algorithm: str = "mdsl"          # fedavg | dsl | multi_dsl | mdsl
    tau: float = 0.9                 # Eq. 5 regularizer (paper §V-A)
    local_epochs: int = 4            # paper §V-A
    batch_size: int = 64             # paper §V-A
    hp: PsoHyperParams = PsoHyperParams()
    pso_every_step: bool = False     # per-step Eq. 8 (unit tests)
    comm: CommConfig = CommConfig()  # wire: compression/channel/aggregation


class SwarmTrainState(NamedTuple):
    """Full state of the distributed system. Worker leaves carry a leading
    C dim."""
    workers: WorkerState             # stacked over C
    global_params: PyTree            # w_t (replicated)
    gbest: GlobalBest                # Eq. 10 view
    sel: SelectionState
    round_idx: Array                 # t
    eta: Array                       # (C,) non-iid degrees (static over rounds)
    residual: PyTree                 # (C, ...) uplink error-feedback state
    ps_residual: PyTree              # PS-side downlink error-feedback state
    phy: comm_phy.PhyState           # per-worker channel state (comm.phy)
    # (C, ...) parked late deltas + staleness ages (comm.straggler);
    # None unless comm.round_deadline_s is set
    buffer: Any = None


def init_state(key: Array, init_params_fn: Callable[[Array], PyTree],
               num_workers: int, eta: Array,
               comm: CommConfig = CommConfig()) -> SwarmTrainState:
    """All workers start from a common global init (Algorithm 1 line 0).
    `comm` seeds the physical-layer state (pathloss profile, unit-gain
    fading) — pass the run's wire config when it uses phy axes."""
    params = init_params_fn(key)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_workers,) + x.shape), params)
    workers = jax.vmap(pso.init_worker_state)(stacked)
    return SwarmTrainState(
        workers=workers,
        global_params=params,
        gbest=pso.init_global_best(params),
        sel=selection.init_selection_state(),
        round_idx=jnp.zeros((), jnp.int32),
        eta=eta,
        residual=comm_compress.init_residual(stacked),
        ps_residual=rounds.init_ps_residual(params),
        phy=comm_phy.init_state(comm, num_workers),
        buffer=comm_straggler.init_buffer(comm, stacked),
    )


def minibatch_rows(data_x: Array, data_y: Array
                   ) -> Callable[[Array], tuple[Array, Array]]:
    """Minibatch rows `i` of one worker's local set: `(data_x[i],
    data_y[i])`, bit for bit, with the images gathered as whole rows of
    their row-major `[n, -1]` view.

    Make it outside the step scan, so the view (one relayout of the set)
    is made once. A gather of the images as XLA lays them out for the
    conv, sample-minor, moves single elements instead of rows.
    """
    rows = data_x.reshape(data_x.shape[0], -1)
    return lambda i: (rows[i].reshape(i.shape + data_x.shape[1:]), data_y[i])


def _local_sgd_epochs(params: PyTree, data_x: Array, data_y: Array,
                      loss_fn: LossFn, lr: Array, cfg: MdslConfig,
                      key: Array) -> PyTree:
    """E epochs of minibatch SGD on one worker's local dataset: step s of
    an epoch trains on rows perm[s*bs:(s+1)*bs] of a fresh permutation."""
    n = data_x.shape[0]
    bs = min(cfg.batch_size, n)
    steps = n // bs
    grad_fn = jax.grad(loss_fn)
    batch = minibatch_rows(data_x, data_y)

    def step(p, i):
        return pso.sgd_step(p, grad_fn(p, *batch(i)), lr), None

    def epoch(params, ekey):
        perm = jax.random.permutation(ekey, n)
        params, _ = jax.lax.scan(step, params,
                                 perm[: steps * bs].reshape(steps, bs))
        return params, None

    params, _ = jax.lax.scan(epoch, params,
                             jax.random.split(key, cfg.local_epochs))
    return params


def _local_update(state: WorkerState, gbest_params: PyTree, data_x: Array,
                  data_y: Array, loss_fn: LossFn, coeffs: PsoCoefficients,
                  lr: Array, cfg: MdslConfig, key: Array,
                  use_pso: bool) -> WorkerState:
    """One worker's round-t local update: PSO terms (Eq. 8) + E SGD epochs."""
    if use_pso and cfg.pso_every_step:
        # Faithful single-step Eq. 8, repeated over minibatches.
        n = data_x.shape[0]
        bs = min(cfg.batch_size, n)
        steps = (n // bs) * cfg.local_epochs
        perm = jax.random.permutation(key, n)
        idx = jnp.resize(perm, (steps * bs,)).reshape(steps, bs)
        grad_fn = jax.grad(loss_fn)
        batch = minibatch_rows(data_x, data_y)

        def step(s, i):
            g = grad_fn(s.params, *batch(i))
            return pso.pso_step(s, gbest_params, g, coeffs, lr, cfg.hp), None

        state, _ = jax.lax.scan(step, state, idx)
        return state

    # Round-level Eq. 8: PSO displacement once + accumulated SGD progress.
    w0 = state.params
    trained = _local_sgd_epochs(w0, data_x, data_y, loss_fn, lr, cfg, key)
    sgd_delta = jax.tree.map(lambda a, b: a - b, trained, w0)
    if not use_pso:  # fedavg
        return state._replace(params=trained,
                              velocity=sgd_delta)

    def leaf(w, v, wl, wg, d):
        v_new = coeffs.c0 * v + coeffs.c1 * (wl - w) + coeffs.c2 * (wg - w) + d
        if cfg.hp.velocity_clip > 0.0:
            v_new = jnp.clip(v_new, -cfg.hp.velocity_clip, cfg.hp.velocity_clip)
        return v_new

    v_next = jax.tree.map(leaf, w0, state.velocity, state.best_params,
                          gbest_params, sgd_delta)
    return state._replace(params=jax.tree.map(jnp.add, w0, v_next),
                          velocity=v_next)


@functools.partial(jax.jit,
                   static_argnames=("loss_fn", "eval_fn", "cfg", "n_params"))
def mdsl_round(state: SwarmTrainState, data_x: Array, data_y: Array,
               eval_x: Array, eval_y: Array, key: Array, *,
               loss_fn: LossFn, eval_fn: LossFn, cfg: MdslConfig,
               n_params: int) -> tuple[SwarmTrainState, RoundTelemetry]:
    """One communication round (Algorithm 1 body).

    data_x/data_y: stacked local datasets (C, n_i, ...); eval_x/eval_y:
    the shared synthetic D_g. Returns the next state and round telemetry.
    """
    C = data_x.shape[0]
    use_pso = cfg.algorithm != "fedavg"
    pipe = rounds.RoundPipeline(algorithm=cfg.algorithm, comm=cfg.comm,
                                num_workers=C, tau=cfg.tau,
                                n_params=n_params)

    ckey, tkey, bkey, qkey, wkey = jax.random.split(key, 5)
    # per-WORKER coefficient draws (classic PSO: each particle has its
    # own random factors). A shared draw hits every worker with the same
    # bad perturbation, leaving the selection rule nothing to filter —
    # per-worker draws are what let Eq. 6 reject derailed workers.
    coeffs = jax.vmap(pso.sample_coefficients)(jax.random.split(ckey, C))
    lr = pso.decayed_lr(cfg.hp, state.round_idx)

    # --- LocalUpdate (Algorithm 1 lines 3-4): bests, update, F_{i,t+1}. ---
    with rounds.stage_span("LocalUpdate"):
        eval_on_dg = lambda p: eval_fn(p, eval_x, eval_y)
        pre_losses = jax.vmap(eval_on_dg)(state.workers.params)
        workers = jax.vmap(pso.update_local_best)(state.workers, pre_losses)

        prev_params = workers.params
        local = functools.partial(_local_update, loss_fn=loss_fn,
                                  lr=lr, cfg=cfg, use_pso=use_pso)
        workers = jax.vmap(
            lambda s, x, y, k, c: local(s, state.gbest.params, x, y, key=k,
                                        coeffs=c)
        )(workers, data_x, data_y, jax.random.split(tkey, C), coeffs)

        # Byzantine workers compute adversarial updates (comm/channel.py);
        # corruption lands in their params so Eq. 6 can see (and reject
        # it).
        workers = workers._replace(params=comm_channel.corrupt_local_updates(
            cfg.comm, prev_params, workers.params, bkey))

        eval_losses = jax.vmap(eval_on_dg)(workers.params)

    # --- ScoreSelect (lines 5-6, Eqs. 4-6). ---
    theta, mask, theta_mean = pipe.select(eval_losses, state.eta,
                                          state.sel.prev_theta_mean)

    # --- Uplink -> Aggregate -> Downlink (lines 7-9, Eq. 7 through the
    # wire). With the default CommConfig this is exactly the seed's
    # masked delta-mean and a dense broadcast. ---
    delta = jax.tree.map(lambda a, b: a - b, workers.params, prev_params)
    out = pipe.wire(delta=delta, theta=theta, mask=mask,
                    global_params=state.global_params,
                    residual=state.residual, ps_residual=state.ps_residual,
                    qkey=qkey, wkey=wkey, phy=state.phy,
                    buffer=state.buffer, round_idx=state.round_idx)

    # --- BestTracking (Eq. 10) + next state. ---
    with rounds.stage_span("BestTracking"):
        global_loss = eval_on_dg(out.global_params)
        gbest = pso.update_global_best(state.gbest, out.global_params,
                                       global_loss)
    next_state = SwarmTrainState(
        workers=workers, global_params=out.global_params, gbest=gbest,
        sel=SelectionState(prev_theta_mean=theta_mean),
        round_idx=state.round_idx + 1, eta=state.eta,
        residual=out.residual, ps_residual=out.ps_residual, phy=out.phy,
        buffer=out.buffer)
    return next_state, pipe.telemetry(losses=eval_losses, theta=theta,
                                      mask=mask, global_loss=global_loss,
                                      outcome=out)


count_params = rounds.count_params
