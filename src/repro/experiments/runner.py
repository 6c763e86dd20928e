"""`run(spec) -> RunResult`: one facade over both training drivers.

The paper driver (C-worker image fleet, `core/mdsl.py`) and the mesh
driver (reduced assigned arch on the active devices, `core/swarm_dist`)
used to live as two hand-wired functions in `launch/train.py` with ~18
positional kwargs each; this module is their single spec-driven home:

    build(spec)   -> Prepared   data/model/state + a uniform step fn
    run(spec)     -> RunResult  the full metrics record (legacy format)
    sweep(specs)  -> [RunResult] scenarios x seeds, artifacts embedding
                                 the full spec

The legacy entry points (`run_paper_experiment`, `run_mesh_training`)
survive as thin deprecated shims in `launch/train.py`, golden-pinned to
emit byte-identical metrics (modulo timing) on the default path.
"""
from __future__ import annotations

import contextlib
import functools
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.budget import (dense_bytes, downlink_config,
                               host_round_bytes, payload_bytes)
from repro.data import partition
from repro.data.synthetic import CIFAR_LIKE, MNIST_LIKE
from repro.experiments.spec import ExperimentSpec, override, to_dict
from repro.obs import trace as obs_trace
from repro.obs.counters import COUNTERS
from repro.obs.events import NULL, Emitter, new_run_id
from repro.obs.sinks import CsvSink, FanoutSink, JsonlSink, default_obs_dir
from repro.obs.trace import span

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts"

# Artifact format version. 1 = pre-obs {"spec", "metrics"}; 2 adds
# top-level "schema" and "events" (the run's JSONL stream path, null
# when obs was disabled). The metrics record itself is unchanged —
# golden pins compare it field-for-field across versions.
SCHEMA_VERSION = 2


def load_result(path: str | Path) -> dict:
    """Load a run artifact, failing loudly on unknown schema versions
    instead of letting downstream scripts KeyError on a shape they were
    never written for. Returns the raw dict with "schema" normalized
    (pre-version artifacts are schema 1)."""
    d = json.loads(Path(path).read_text())
    schema = d.get("schema", 1)
    if schema not in (1, 2):
        raise ValueError(
            f"{path}: artifact schema {schema!r} is newer than this "
            f"reader (knows 1..{SCHEMA_VERSION}) — upgrade the repo or "
            f"re-run the experiment")
    if not isinstance(d.get("metrics"), dict):
        raise ValueError(f"{path}: not a run artifact (no metrics dict)")
    d["schema"] = schema
    return d


def _noniid2_groups(C: int) -> list[tuple[int, float]]:
    """Fig. 2 fleet (20 @ 0.1, 15 @ 0.5, 10 @ 1.0, 5 @ 10.0), scaled
    proportionally to C workers (quick-mode benchmarks use C < 50)."""
    fracs = [(0.4, 0.1), (0.3, 0.5), (0.2, 1.0), (0.1, 10.0)]
    counts = [max(1, round(f * C)) for f, _ in fracs]
    counts[0] += C - sum(counts)  # absorb rounding into the largest group
    return [(c, a) for c, (_, a) in zip(counts, fracs)]


def _dirichlet(alpha: float):
    return lambda key, C, spec, n: partition.dirichlet_partition(
        key, C, alpha, spec, n_local=n)


# mutable on purpose: legacy callers (benchmarks/fig1_metric.py) used to
# monkeypatch entries; new code sets DataSpec.alpha instead
CASES = {
    "iid": lambda key, C, spec, n: partition.iid_partition(
        key, C, spec, n_local=n),
    "noniid1": _dirichlet(0.5),
    "noniid2": lambda key, C, spec, n: partition.mixed_dirichlet_partition(
        key, _noniid2_groups(C), spec, n_local=n),
}
IMAGE_SPECS = {"mnist_like": MNIST_LIKE, "cifar_like": CIFAR_LIKE}


def make_case_data(case: str, dataset: str, num_workers: int, seed: int,
                   n_local: int = 512, alpha: Optional[float] = None):
    """Partitioned fleet data for one case. `alpha` overrides the
    Dirichlet concentration of the noniid1 case (DataSpec.alpha)."""
    spec = IMAGE_SPECS[dataset]
    case_fn = (_dirichlet(alpha) if case == "noniid1" and alpha is not None
               else CASES[case])
    return case_fn(jax.random.PRNGKey(seed), num_workers, spec, n_local), spec


class Prepared(NamedTuple):
    """A built (but not yet run) experiment: everything `run` loops over.

    `step(state, key) -> (state, telemetry, key)` advances one
    communication round, consuming randomness exactly as the legacy
    drivers did (so default-path runs stay golden-pinned)."""
    spec: ExperimentSpec
    state: Any
    step: Callable[[Any, jax.Array], tuple[Any, Any, jax.Array]]
    key: jax.Array
    n_params: int
    aux: dict


class RunResult(NamedTuple):
    """A finished run: the spec that produced it + the metrics record
    (the record is the legacy metrics-JSON dict, unchanged) + the path
    of the run's obs event stream (None when obs was disabled)."""
    spec: ExperimentSpec
    record: dict
    events_path: Optional[str] = None

    def to_dict(self) -> dict:
        return {"schema": SCHEMA_VERSION, "spec": to_dict(self.spec),
                "metrics": self.record, "events": self.events_path}

    def save(self, path: str | Path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_dict(), indent=1))
        return path


# ---------------------------------------------------------------------------
# Paper driver (§V: C edge workers on partitioned synthetic image data)
# ---------------------------------------------------------------------------

def _prepare_paper(spec: ExperimentSpec) -> Prepared:
    from repro.configs.paper_cnn import paper_cnn, paper_resnet
    from repro.core import losses as losses_mod
    from repro.core import mdsl, noniid
    from repro.core.mdsl import MdslConfig

    d, a, r = spec.data, spec.algo, spec.run
    # each set-up span ends on the device work it started, so its
    # seconds are its own and not the next span's
    with span("setup.data"):
        data, img_spec = make_case_data(d.case, d.dataset, d.num_workers,
                                        r.seed, d.n_local, alpha=d.alpha)
        jax.block_until_ready(data)
    L = img_spec.num_classes
    coeffs = (noniid.EtaCoefficients(*d.eta_coeffs) if d.eta_coeffs
              else (noniid.MNIST_COEFFS if d.dataset == "mnist_like"
                    else noniid.CIFAR10_COEFFS))
    with span("setup.eta"):
        eta = jax.block_until_ready(noniid.noniid_degree_from_labels(
            data.y, data.global_y, L, coeffs))

    cfg = MdslConfig(algorithm=a.algorithm, tau=a.tau,
                     local_epochs=a.local_epochs, batch_size=a.batch_size,
                     hp=a.hp, comm=spec.comm)
    with span("setup.init"):
        img_model = (paper_cnn(img_spec, spec.model.width_mult)
                     if spec.model.name == "cnn"
                     else paper_resnet(img_spec, spec.model.width_mult))
        key = jax.random.PRNGKey(r.seed + 1)
        state = jax.block_until_ready(mdsl.init_state(
            key, img_model.init, d.num_workers, eta, comm=spec.comm))
    n_params = mdsl.count_params(state.global_params)

    loss_fn = lambda p, x, y: losses_mod.cross_entropy_loss(
        img_model.apply(p, x), y, L)
    eval_fn = lambda p, x, y: losses_mod.rmse_loss(  # Eq. 3 scoring on D_g
        img_model.apply(p, x), y, L)

    @jax.jit
    def test_accuracy(params):
        return losses_mod.accuracy(img_model.apply(params, data.test_x),
                                   data.test_y)

    def step(state, key):
        with span("round.key"):
            key, rkey = jax.random.split(key)
        with span("round.dispatch"):
            state, metrics = mdsl.mdsl_round(
                state, data.x, data.y, data.global_x, data.global_y, rkey,
                loss_fn=loss_fn, eval_fn=eval_fn, cfg=cfg, n_params=n_params)
        return state, metrics, key

    return Prepared(spec=spec, state=state, step=step, key=key,
                    n_params=n_params,
                    aux={"data": data, "model": img_model, "eta": eta,
                         "cfg": cfg, "test_accuracy": test_accuracy})


class _PopulationState(NamedTuple):
    """Engine state wrapped by the population scheduler: the K-cohort
    engine state, the O(P)-scalar device registry, the device ids
    holding the K slots, and the host round counter driving the lazy
    catch-up arithmetic."""
    inner: Any               # SwarmTrainState over the K cohort slots
    table: Any               # population.PopulationTable over P devices
    cohort: jax.Array        # (K,) int32 device ids seated in the slots
    t: int                   # next round index (host-side)

    @property
    def global_params(self):
        return self.inner.global_params


def _wrap_population(prep: Prepared) -> Prepared:
    """Lift a prepared K-worker paper run into a P-device fleet.

    Per round: fold POP_SALT off the round key (the inner engine's
    legacy key chain is never advanced), sample the K-cohort, gather
    its channel rows with lazy fading catch-up, reseat changed slots
    (fresh devices join at the current global model with zero velocity
    and reset personal bests — `pso.init_worker_state` semantics — and
    a zero uplink EF residual), run the inner round UNCHANGED, then
    scatter the cohort's post-round scalars back into the table. Model
    state stays O(K); the registry stays O(P) scalars.

    Degenerate anchor: population == cohort_size under the uniform
    policy samples the identity cohort, the reseat mask is all-False
    (every `jnp.where` returns its stored operand bitwise), and the
    gather's lag-0 guards pass the scattered channel rows back
    untouched — such runs are bit-identical to the unwrapped engine.

    Known limitation (documented in docs/population.md): worker data
    partitions and the eta non-iid degrees are SLOT-resident, not
    device-resident — device p seated in slot k trains on partition k.
    The fleet axis models channels, schedules, and staleness, not P
    distinct datasets."""
    from repro.core import population as pop
    from repro.core.pso import WorkerState

    spec = prep.spec
    f, comm = spec.fleet, spec.comm
    K = spec.data.num_workers
    inner_step = prep.step
    schedule = functools.partial(pop.schedule, comm=comm, cohort_size=K,
                                 policy=f.cohort_policy)

    @jax.jit
    def reseat(inner, changed, phy):
        def mix(fresh, old):
            return jax.tree.map(
                lambda fl, ol: jnp.where(
                    changed.reshape((-1,) + (1,) * (fl.ndim - 1)), fl, ol),
                fresh, old)
        g = inner.global_params
        bcast = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (K,) + x.shape), g)
        inf = jnp.full((K,), jnp.inf, jnp.float32)
        fresh_workers = WorkerState(
            params=bcast, velocity=jax.tree.map(jnp.zeros_like, bcast),
            best_params=bcast, best_loss=inf, prev_loss=inf)
        buf = inner.buffer
        if buf is not None:
            # a parked late delta belongs to the device that uploaded
            # it: clear reseated slots so a stranger's stale update
            # can't drain into the new occupant's rounds
            buf = buf._replace(
                delta=mix(jax.tree.map(jnp.zeros_like, buf.delta),
                          buf.delta),
                age=jnp.where(changed, 0, buf.age))
        return inner._replace(
            workers=mix(fresh_workers, inner.workers),
            residual=mix(jax.tree.map(jnp.zeros_like, inner.residual),
                         inner.residual),
            phy=phy, buffer=buf)

    @jax.jit
    def scatter(table, idx, inner, theta, round_idx):
        return pop.scatter_round(
            table, idx, inner.phy, theta,
            pop.residual_norms(inner.residual), round_idx)

    def step(state, key):
        with span("round.schedule"):
            t = jnp.int32(state.t)
            pkey = jax.random.fold_in(key, pop.POP_SALT)
            idx, phy = schedule(state.table, t, pkey)
        with span("round.reseat"):
            inner = reseat(state.inner, idx != state.cohort, phy)
        inner, metrics, key = inner_step(inner, key)
        with span("round.scatter"):
            table = scatter(state.table, idx, inner, metrics.theta, t)
        return (_PopulationState(inner=inner, table=table, cohort=idx,
                                 t=state.t + 1),
                metrics._replace(cohort=idx), key)

    table = pop.init_table(comm, f.population)
    state0 = _PopulationState(
        inner=prep.state, table=table,
        cohort=jnp.arange(K, dtype=jnp.int32), t=0)
    aux = dict(prep.aux, population=f.population,
               table_bytes=pop.table_bytes(table))
    return prep._replace(state=state0, step=step, aux=aux)


def _round_window(profiler, t: int):
    """The per-round profiler window (nullcontext when not profiling)."""
    return profiler.round(t) if profiler is not None \
        else contextlib.nullcontext()


def _run_paper(prep: Prepared, verbose: bool, em=NULL,
               profiler=None) -> dict:
    spec, comm = prep.spec, prep.spec.comm
    d, a, r = spec.data, spec.algo, spec.run
    state, key = prep.state, prep.key
    test_accuracy = prep.aux["test_accuracy"]
    record = {"algorithm": a.algorithm, "case": d.case, "dataset": d.dataset,
              "model": prep.aux["model"].name, "rounds": r.rounds,
              "num_workers": d.num_workers, "tau": a.tau, "seed": r.seed,
              "n_params": prep.n_params,
              "eta": np.asarray(prep.aux["eta"]).tolist(),
              "comm": comm._asdict(),
              "payload_bytes_per_worker": payload_bytes(
                  comm, state.global_params),
              "dense_bytes_per_worker": dense_bytes(state.global_params),
              "downlink_bytes_per_worker": payload_bytes(
                  downlink_config(comm), state.global_params),
              "acc": [], "global_loss": [], "selected": [], "delivered": [],
              "uploaded_params": [], "bytes_up": [], "bytes_down": [],
              "airtime_s": [], "energy_j": [], "mean_snr_db": [],
              "round_time_s": []}
    if spec.fleet.population:
        record["population"] = spec.fleet.population
        record["cohort_size"] = d.num_workers
        record["cohort_policy"] = spec.fleet.cohort_policy

    metrics = None
    for t in range(r.rounds):
        with span("round", round_idx=t) as round_span:
            with _round_window(profiler, t):
                with span("Step", round_idx=t):
                    state, metrics, key = prep.step(state, key)
                    if em.active:
                        # host sync so the Step span covers device time;
                        # obs-off runs keep the legacy async dispatch
                        jax.block_until_ready(metrics)
                with span("Eval", round_idx=t):
                    acc = float(test_accuracy(state.global_params))
            # under fault injection only alive selected workers
            # transmit: the exact byte/energy accounting keys off that
            # count
            transmitted = getattr(metrics, "transmitted", None)
            up, down = host_round_bytes(
                comm,
                selected=(transmitted if transmitted is not None
                          else metrics.selected_count),
                bytes_up_jit=metrics.bytes_up,
                payload_up=record["payload_bytes_per_worker"],
                payload_down=record["downlink_bytes_per_worker"],
                num_workers=d.num_workers)
            # ONE row dict feeds both the artifact history and the event
            # stream, so the JSONL round metrics are bit-equal to the
            # artifact by construction
            row = {"acc": acc, "global_loss": float(metrics.global_loss),
                   "selected": int(metrics.selected_count),
                   "delivered": int(metrics.delivered_count),
                   "uploaded_params": float(metrics.uploaded_params),
                   "bytes_up": up, "bytes_down": down,
                   "airtime_s": float(metrics.airtime_s),
                   "energy_j": float(metrics.energy_j),
                   "mean_snr_db": float(metrics.mean_snr_db)}
        row["round_time_s"] = round_span.dur_s
        if transmitted is not None:
            row["transmitted"] = int(transmitted)
        for k in ("late", "drained", "buffered", "held"):
            v = getattr(metrics, k, None)
            if v is not None:
                row[k] = int(v)
        if getattr(metrics, "cohort", None) is not None:
            row["cohort"] = np.asarray(metrics.cohort).tolist()
        for k, v in row.items():
            record.setdefault(k, []).append(v)
        em.round(t, row)
        if row.get("held"):
            em.log(f"[straggler] round {t}: quorum hold — w_t frozen "
                   f"(late={row.get('late', 0)} "
                   f"buffered={row.get('buffered', 0)})")
        if verbose and (t % r.log_every == 0 or t == r.rounds - 1):
            em.log(f"[{a.algorithm}/{d.case}/{d.dataset}] "
                   f"round {t + 1}/{r.rounds} "
                   f"acc={acc:.3f} loss={row['global_loss']:.4f} "
                   f"selected={row['selected']}/{d.num_workers} "
                   f"up={float(metrics.bytes_up) / 2**20:.2f}MiB "
                   f"air={row['airtime_s']:.3f}s "
                   f"e={row['energy_j']:.3f}J")
    record["final_acc"] = record["acc"][-1]
    record["best_acc"] = max(record["acc"])
    record["total_uploaded_params"] = float(sum(record["uploaded_params"]))
    record["total_bytes_up"] = float(sum(record["bytes_up"]))
    record["total_bytes_down"] = float(sum(record["bytes_down"]))
    record["total_airtime_s"] = float(sum(record["airtime_s"]))
    record["total_energy_j"] = float(sum(record["energy_j"]))
    # adaptive tiers mix payloads per worker: the fleet-mean ratio comes
    # from the in-jit accounting, matching the bytes_up column
    record["compression_ratio"] = (
        float(metrics.compression_ratio) if comm.adaptive_bits
        else record["dense_bytes_per_worker"]
        / record["payload_bytes_per_worker"])
    return record


# ---------------------------------------------------------------------------
# Mesh driver (production path: reduced assigned arch, jitted SPMD round)
# ---------------------------------------------------------------------------

def _prepare_mesh(spec: ExperimentSpec) -> Prepared:
    from repro.configs.base import get_arch
    from repro.core import swarm_dist
    from repro.core.swarm_dist import DistSwarmConfig
    from repro.models.transformer import Transformer

    m, a, r = spec.model, spec.algo, spec.run
    W = spec.data.num_workers
    cfg = get_arch(m.name)
    if m.reduced:
        cfg = cfg.reduced()
    dcfg = DistSwarmConfig(worker_axes=(), num_spatial=W,
                           local_steps=a.local_steps, tau=a.tau,
                           hp=a.hp, comm=spec.comm)
    key = jax.random.PRNGKey(r.seed)
    with span("setup.init"):
        model = Transformer(cfg)
        params = model.init(key)
        # jitted so every state leaf gets its own buffer (global_params
        # and gbest_params start as one array, which the donating step
        # refuses)
        state = jax.block_until_ready(jax.jit(functools.partial(
            swarm_dist.init_state, cfg=dcfg))(params))
    build = (swarm_dist.fedavg_train_step if a.algorithm == "fedavg"
             else swarm_dist.build_train_step)
    # donate the state as launch/steps.py does: at published widths it is
    # most of the chip's memory and cannot be held twice
    step_fn = jax.jit(build(model.loss, dcfg), donate_argnums=(0,))

    B, S = m.per_worker_batch, m.seq_len

    def batch_for(k, lead):
        # ids uniform over the model's vocabulary (a sliced vocabulary's
        # rows); the loss scores position t on labels[t + 1] itself
        toks = jax.random.randint(k, lead + (B, S), 0, cfg.vocab_size)
        out = {"tokens": toks, "labels": toks}
        if cfg.input_mode == "tokens+prefix":
            out["prefix"] = jnp.zeros(lead + (B, cfg.prefix_len, cfg.d_model),
                                      jnp.dtype(cfg.dtype))
        if cfg.encoder_layers:
            out["frames"] = jax.random.normal(
                k, lead + (B, cfg.encoder_memory_len, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return out

    def step(state, key):
        with span("round.key"):
            key, k1, k2, k3 = jax.random.split(key, 4)
        with span("round.batch"):
            local, shared = batch_for(k1, (W,)), batch_for(k2, ())
        with span("round.dispatch"):
            state, info = step_fn(state, local, shared, k3)
        return state, info, key

    from repro.core import rounds
    return Prepared(spec=spec, state=state, step=step, key=key,
                    n_params=rounds.count_params(params),
                    aux={"model": model, "arch_cfg": cfg, "dcfg": dcfg,
                         "params": params})


def _run_mesh(prep: Prepared, verbose: bool, em=NULL,
              profiler=None) -> dict:
    from repro.checkpoint import CheckpointManager

    spec = prep.spec
    m, r = spec.model, spec.run
    dcfg = prep.aux["dcfg"]
    W = spec.data.num_workers
    state, key = prep.state, prep.key
    mgr = CheckpointManager(r.ckpt_dir) if r.ckpt_dir else None

    payload = payload_bytes(dcfg.comm, prep.aux["params"])
    down_payload = payload_bytes(downlink_config(dcfg.comm),
                                 prep.aux["params"])
    record = {"arch": m.name, "reduced": m.reduced, "steps": r.rounds,
              "comm": dcfg.comm._asdict(),
              "payload_bytes_per_worker": payload,
              "downlink_bytes_per_worker": down_payload, "global_loss": [],
              "worker_losses": [], "selected": [], "delivered": [],
              "bytes_up": [], "bytes_down": [], "airtime_s": [],
              "energy_j": [], "mean_snr_db": [], "step_time_s": []}
    for i in range(r.rounds):
        with span("round", round_idx=i) as round_span:
            with _round_window(profiler, i):
                with span("Step", round_idx=i):
                    state, info, key = prep.step(state, key)
                    if em.active:
                        jax.block_until_ready(info)
            gl = float(info.global_loss)
            transmitted = getattr(info, "transmitted", None)
            up, down = host_round_bytes(
                dcfg.comm,
                selected=(transmitted if transmitted is not None
                          else info.mask.sum()),
                bytes_up_jit=info.bytes_up,
                payload_up=payload, payload_down=down_payload,
                num_workers=W)
            # one row feeds both artifact history and event stream (see
            # _run_paper) — bit-equal by construction
            row = {"global_loss": gl,
                   "worker_losses": np.asarray(info.losses).tolist(),
                   "selected": float(info.mask.sum()),
                   "delivered": float(info.delivered),
                   "bytes_up": up, "bytes_down": down,
                   "airtime_s": float(info.airtime_s),
                   "energy_j": float(info.energy_j),
                   "mean_snr_db": float(info.mean_snr_db)}
        row["step_time_s"] = round_span.dur_s
        if transmitted is not None:
            row["transmitted"] = float(transmitted)
        for k in ("late", "drained", "buffered", "held"):
            v = getattr(info, k, None)
            if v is not None:
                row[k] = float(v)
        for k, v in row.items():
            record.setdefault(k, []).append(v)
        em.round(i, row)
        if verbose:
            em.log(f"[mesh/{m.name}] step {i + 1}/{r.rounds} "
                   f"global_loss={gl:.4f} "
                   f"selected={int(info.mask.sum())}/{W} "
                   f"air={row['airtime_s']:.3f}s "
                   f"e={row['energy_j']:.3f}J")
        if mgr is not None:
            mgr.save(i, state.global_params, metadata={"arch": m.name})
    if mgr is not None:
        record["ckpt_steps"] = mgr.all_steps()
    record["total_airtime_s"] = float(sum(record["airtime_s"]))
    record["total_energy_j"] = float(sum(record["energy_j"]))
    return record


# ---------------------------------------------------------------------------
# Facade
# ---------------------------------------------------------------------------

def build(spec: ExperimentSpec) -> Prepared:
    """Validate + materialize a spec into data/model/state and one
    uniform `step` callable, without running any rounds."""
    spec = spec.validate()
    if spec.model.kind != "paper":
        return _prepare_mesh(spec)
    prep = _prepare_paper(spec)
    if spec.fleet.population:
        prep = _wrap_population(prep)
    return prep


def _obs_emitter(spec: ExperimentSpec, engine: str):
    """RunSpec.obs -> an emitter (NULL when disabled). The stream lands
    under `obs.dir` (default artifacts/obs/) as <run_id>.jsonl, plus a
    per-round CSV next to it when `obs.csv` is set."""
    o = spec.run.obs
    if not o.enabled:
        return NULL
    run_id = new_run_id(f"{spec.name or engine}__s{spec.run.seed}")
    base = Path(o.dir) if o.dir else default_obs_dir()
    sink = JsonlSink(base / f"{run_id}.jsonl")
    if o.csv:
        sink = FanoutSink(sink, CsvSink(base / f"{run_id}.csv"))
    return Emitter(run_id, sink)


def _run_totals(record: dict) -> dict:
    """Cumulants for the RunEnd event, read off the finished record."""
    totals = {}
    for k in ("final_acc", "best_acc", "total_bytes_up",
              "total_bytes_down", "total_airtime_s", "total_energy_j"):
        if k in record:
            totals[k] = record[k]
    if "final_acc" not in totals and record.get("global_loss"):
        totals["final_loss"] = record["global_loss"][-1]
    return totals


def run(spec: ExperimentSpec, verbose: bool = True) -> RunResult:
    """Execute a spec end-to-end: the single front door subsuming the
    legacy `run_paper_experiment` / `run_mesh_training` drivers.

    With `run.obs.enabled` the whole run streams typed events (see
    repro.obs): run_start with the full spec, a per-round RoundEvent
    bit-equal to the artifact history, per-stage spans (installed BEFORE
    the first step so the RoundPipeline stages are timed during the
    round-0 jit trace), optional jax.profiler round windows, and a
    run_end with cumulative totals, the compile counters among them.
    The set-up spans are recorded while the run is built and follow
    run_start on the stream."""
    spec = spec.validate()
    engine = "paper" if spec.model.kind == "paper" else "mesh"
    em = _obs_emitter(spec, engine)
    counted = COUNTERS.snapshot()
    with obs_trace.recording() as setup_spans:
        prep = build(spec)
    spec = prep.spec
    tracer = profiler = None
    if em.active:
        o = spec.run.obs
        em.run_start(scenario=spec.name, seed=spec.run.seed, engine=engine,
                     num_workers=spec.data.num_workers,
                     rounds=spec.run.rounds, n_params=prep.n_params,
                     population=spec.fleet.population or 0,
                     cohort=(spec.data.num_workers
                             if spec.fleet.population else 0),
                     spec=to_dict(spec))
        for sp in setup_spans:
            em.stage(sp.name, sp.dur_s, start_s=em.clock.at(sp.start),
                     parent=sp.parent)
        tracer = obs_trace.StageTracer(em, phase="trace",
                                       stages=o.stage_spans)
        if o.profile_dir:
            profiler = obs_trace.RoundProfiler(
                o.profile_dir, start=min(1, spec.run.rounds - 1),
                count=o.profile_rounds, emitter=em)
    try:
        with obs_trace.activated(tracer):
            record = (_run_paper(prep, verbose, em, profiler)
                      if engine == "paper"
                      else _run_mesh(prep, verbose, em, profiler))
        if profiler is not None:     # window longer than the run
            profiler.stop()
    except BaseException:
        if em.active:
            try:
                if profiler is not None:
                    profiler.stop()
            finally:
                em.run_end(rounds=0, status="error")
                em.close()
        raise
    em.run_end(rounds=spec.run.rounds,
               totals={**_run_totals(record), **COUNTERS.since(counted)})
    em.close()
    return RunResult(spec=spec, record=record, events_path=em.path)


def default_out(spec: ExperimentSpec) -> Path:
    """Artifact path for one run. Scenario runs land under
    artifacts/experiments/<name>__s<seed>.json; anonymous specs keep the
    legacy artifacts/train naming."""
    if spec.run.out:
        return Path(spec.run.out)
    if spec.name:
        safe = spec.name.replace("/", "-")
        return ARTIFACTS / "experiments" / f"{safe}__s{spec.run.seed}.json"
    if spec.model.kind == "paper":
        return (ARTIFACTS / "train" /
                f"{spec.algo.algorithm}__{spec.data.case}"
                f"__{spec.data.dataset}__s{spec.run.seed}.json")
    return (ARTIFACTS / "train" /
            f"mesh__{spec.model.name}__s{spec.run.seed}.json")


def _sweep_task(spec_dict: dict, path: str, verbose: bool) -> dict:
    """One (scenario, seed) cell, spec passed as its JSON dict so the
    task pickles cleanly into a ProcessPoolExecutor worker. Runs the
    spec, saves its artifact, returns {record, events, wall_s}. Obs
    streams are process-local by design (run ids embed the pid), so a
    pool cell needs no cross-process file coordination."""
    from repro.experiments.spec import from_dict
    t0 = time.time()
    res = run(from_dict(spec_dict), verbose=verbose)
    res.save(path)
    return {"record": res.record, "events": res.events_path,
            "wall_s": time.time() - t0}


def _cell_name(spec: ExperimentSpec) -> str:
    return spec.name or f"{spec.algo.algorithm}/{spec.data.case}"


def _sweep_report(spec: ExperimentSpec, record: dict, path: Path,
                  wall_s: float, events: Optional[str]) -> None:
    """Per-cell stderr line: headline metric, wall-time, artifact, and
    (when obs is on) the cell's event stream — grid runs stay
    attributable without re-opening artifacts."""
    final = record.get("final_acc", record["global_loss"][-1])
    ev = f" events={events}" if events else ""
    print(f"[sweep] {_cell_name(spec)} s{spec.run.seed}: {final:.4f} "
          f"wall={wall_s:.1f}s -> {path}{ev}",
          file=sys.stderr, flush=True)


def sweep(specs, seeds=(0,), out_dir: str | Path | None = None,
          verbose: bool = False, jobs: int = 1) -> list[RunResult]:
    """Fan scenarios x seeds into consistently named artifacts, each
    embedding the full spec next to its metrics. Any `run.out` on the
    input specs is cleared: per-(scenario, seed) naming wins, so one
    fixed path cannot clobber the rest of the sweep.

    `jobs > 1` fans the (scenario x seed) grid over a
    ProcessPoolExecutor — each cell is an independent single-host run
    writing its own artifact file, so the paper grid (4 algos x 3 cases
    x 5 seeds) runs in one command (`launch/train.py --sweep ...
    --jobs N`). Results come back in grid order either way. An
    accelerator belongs to one process, so `jobs > 1` is refused unless
    the run is pinned to the CPU (`JAX_PLATFORMS=cpu`)."""
    if jobs > 1 and (jax.config.jax_platforms or "") != "cpu":
        # read from the config, not jax.devices(): that would start a
        # backend in this parent and hold the chip the children need
        raise ValueError(
            f"sweep(jobs={jobs}) would start {jobs} processes that each "
            f"open the accelerator, which serves one process at a time: "
            f"run with jobs=1, or pin the sweep to the CPU with "
            f"JAX_PLATFORMS=cpu")
    cells: list[tuple[ExperimentSpec, Path]] = []
    for spec in specs:
        for seed in seeds:
            s = override(spec, f"run.seed={seed}", "run.out=none")
            path = default_out(s)
            if out_dir is not None:
                path = Path(out_dir) / path.name
            cells.append((s, path))

    # sweep-level summary stream: one SweepEvent per finished cell (each
    # cell also writes its own run stream) — the grid is derivable from
    # streams alone
    sem = NULL
    if cells and cells[0][0].run.obs.enabled:
        first = cells[0][0]
        base = (Path(first.run.obs.dir) if first.run.obs.dir
                else default_obs_dir())
        rid = new_run_id(f"sweep__{first.name or 'grid'}")
        sem = Emitter(rid, JsonlSink(base / f"{rid}.jsonl"))

    def finish_cell(s, path, record, events, wall_s, results):
        sem.sweep_cell(_cell_name(s), seed=s.run.seed,
                       final=record.get("final_acc",
                                        record["global_loss"][-1]),
                       wall_s=round(wall_s, 3), artifact=str(path),
                       events=events)
        if not verbose:
            _sweep_report(s, record, path, wall_s, events)
        results.append(RunResult(spec=s, record=record,
                                 events_path=events))

    results: list[RunResult] = []
    try:
        if jobs <= 1:
            for s, path in cells:
                t0 = time.time()
                res = run(s, verbose=verbose)
                res.save(path)
                finish_cell(s, path, res.record, res.events_path,
                            time.time() - t0, results)
            return results

        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        # fork would copy this process's initialized XLA runtime into
        # the workers (thread-lock deadlocks); spawn gives each cell a
        # clean interpreter
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=jobs, mp_context=ctx) as ex:
            futs = [ex.submit(_sweep_task, to_dict(s), str(path), verbose)
                    for s, path in cells]
            for (s, path), fut in zip(cells, futs):
                out = fut.result()
                finish_cell(s, path, out["record"], out["events"],
                            out["wall_s"], results)
        return results
    finally:
        if sem.active:
            sem.run_end(rounds=len(results),
                        status="ok" if len(results) == len(cells)
                        else "error")
            sem.close()


def spec_from_paper_kwargs(algorithm="mdsl", case="noniid1",
                           dataset="mnist_like", rounds=20, num_workers=50,
                           model="cnn", width_mult=8, tau=0.9,
                           local_epochs=4, batch_size=64, lr=0.01,
                           velocity_clip=0.1, seed=0, eta_coeffs=None,
                           n_local=512, log_every=1,
                           comm=None) -> ExperimentSpec:
    """Map the legacy `run_paper_experiment(...)` kwargs onto a spec
    (the deprecated shim and older callers route through this)."""
    from repro.comm.budget import CommConfig
    from repro.core.pso import PsoHyperParams
    from repro.experiments.spec import (AlgoSpec, DataSpec, ModelSpec,
                                        RunSpec)
    return ExperimentSpec(
        data=DataSpec(dataset=dataset, case=case, num_workers=num_workers,
                      n_local=n_local,
                      eta_coeffs=tuple(eta_coeffs) if eta_coeffs else None),
        model=ModelSpec(kind="paper", name=model, width_mult=width_mult),
        algo=AlgoSpec(algorithm=algorithm, tau=tau,
                      local_epochs=local_epochs, batch_size=batch_size,
                      hp=PsoHyperParams(learning_rate=lr,
                                        velocity_clip=velocity_clip)),
        comm=(comm or CommConfig()),
        run=RunSpec(rounds=rounds, seed=seed, log_every=log_every))


def spec_from_mesh_kwargs(arch, steps=5, reduced=True, seq_len=128,
                          per_worker_batch=2, num_spatial=2, ckpt_dir=None,
                          seed=0, comm=None) -> ExperimentSpec:
    """Map the legacy `run_mesh_training(...)` kwargs onto a spec."""
    from repro.comm.budget import CommConfig
    from repro.core.pso import PsoHyperParams
    from repro.experiments.spec import (AlgoSpec, DataSpec, ModelSpec,
                                        RunSpec)
    return ExperimentSpec(
        data=DataSpec(num_workers=num_spatial),
        model=ModelSpec(kind="mesh", name=arch, reduced=reduced,
                        seq_len=seq_len, per_worker_batch=per_worker_batch),
        algo=AlgoSpec(algorithm="mdsl", tau=0.9, local_steps=1,
                      hp=PsoHyperParams(learning_rate=3e-3,
                                        velocity_clip=1.0)),
        comm=(comm or CommConfig()),
        run=RunSpec(rounds=steps, seed=seed,
                    ckpt_dir=str(ckpt_dir) if ckpt_dir else None))


# dataclasses imported for callers composing specs around the runner
__all__ = ["ARTIFACTS", "SCHEMA_VERSION", "Prepared", "RunResult", "build",
           "load_result", "run", "sweep", "default_out", "make_case_data",
           "spec_from_paper_kwargs", "spec_from_mesh_kwargs"]
