"""The Kanana-2-30B-A3B cell's benchmark files on the CPU: the MLA / MoE
readers on a hand-made trace with forward, rematerialised and backward
scope paths, the mesh MFU reader, the work count by hand and against the
program's own weights, and the output check at a reduced size."""
import importlib.util
import json

import jax
import pytest

import calibrate
import run
import trace_reduce as tr
from conftest import BENCH

CELL = "kanana-2-30b-a3b.w2-4k"
SEED = 2**31 + 23
REDUCED = ("model.reduced=true", "model.seq_len=32")
# The cell's limits are calibrated at the published widths on the chip
# (PERF.md). At width 128 and 32 tokens bfloat16 rounding moves the
# losses ~10x more (sound `loss` up to 3.2e-3 on the CPU), so the
# reduced check holds the run to these, which the float8 control still
# fails (its `loss` 1.6e-2, `update` 0.37).
REDUCED_LIMITS = {"loss": 0.01, "worker_loss": 0.01, "update": 0.05,
                  "change": 0.05, "best": 0.05, "gbest": 0.05, "decisions": 0}


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def work():
    return (_load(BENCH / "work" / "kanana-2-30b-a3b.py"),
            json.loads((BENCH / "configs" / "kanana-2-30b-a3b.json").read_text()))


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

LU = "jit(train_step)/LocalUpdate/while/body/closed_call"
BWD = LU + "/transpose(jvp())/while/body/closed_call"
OPS = [  # (start us, dur us, scope) as the chip's compiled step names them
    (0, 10, LU + "/checkpoint/MLA/dot_general"),                   # forward
    (10, 5, "jit(train_step)/jvp(MLA)/tanh"),                      # forward
    (15, 7, BWD + "/checkpoint/rematted_computation/MLA/mul"),     # remat
    (22, 4, LU + "/transpose(jvp(MLA))/dot_general"),              # backward
    (26, 1, LU + "/checkpoint/MLA/transpose;checkpoint/MLA/reshape"),
    (27, 6, LU + "/checkpoint/MoE/ragged-dot"),
    (33, 3, LU + "/transpose(jvp(MoE))/convert_element_type"),
    (36, 2, BWD + "/checkpoint/rematted_computation/MoE/gather"),
    (38, 8, BWD),                                                  # neither
    (46, 2, LU + "/MLAX/add"),                                     # neither
    (48, 1, "ragged-dot-none:"),              # grouped product, no scope
]
KERNELS = {"ragged-dot-none:"}


def _doc():
    meta = [{"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 9, "name": "process_name",
             "args": {"name": "/host:CPU"}}]
    ops = [{"ph": "X", "pid": 3, "tid": 3, "ts": s, "dur": d,
            "name": (f"ragged-dot-none.{i}" if scope in KERNELS
                     else f"fusion.{i}"),
            "args": {"hlo_category": ("custom-call" if scope in KERNELS
                                      else "loop fusion"), "tf_op": scope}}
           for i, (s, d, scope) in enumerate(OPS)]
    spans = [{"ph": "X", "pid": 9, "tid": 1, "ts": t, "dur": 25,
              "name": "bench.step"} for t in (0, 25)]
    return {"traceEvents": meta + ops + spans}


def _reading(work_cfg=None):
    red = tr.reduce_trace(tr.parse(_doc()))
    w, cfg = work_cfg if work_cfg else (None, None)
    spec = {"model": {"per_worker_batch": 1, "seq_len": 4096},
            "algo": {"local_steps": 1}, "data": {"num_workers": 2},
            "comm": {"compressor": "identity"}}
    return {"reduced": red, "work": w, "cfg": cfg, "spec": spec,
            "peaks": {"bf16_flops_per_s": 197e12}}


@pytest.mark.parametrize("metric,want_us", [
    ("mla_ms", 10 + 5 + 7 + 4 + 1), ("moe_ms", 6 + 3 + 2 + 1)])
def test_scope_readers_count_forward_remat_and_backward(metric, want_us):
    reader = _load(BENCH / "metrics" / f"{metric}.py")
    r = _reading()
    assert r["reduced"].rounds == 2
    assert reader.read(r) == pytest.approx(want_us * 1e-3 / 2)


@pytest.mark.parametrize("metric", ["mla_ms", "moe_ms"])
def test_scope_readers_give_nothing_without_their_scope(metric):
    reader = _load(BENCH / "metrics" / f"{metric}.py")
    doc = _doc()
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if metric[:3].upper() not in
                          e.get("args", {}).get("tf_op", "").upper()
                          and "ragged" not in e.get("name", "")]
    r = _reading()
    r["reduced"] = tr.reduce_trace(tr.parse(doc))
    assert reader.read(r) is None


def test_mesh_round_mfu(work):
    reader = _load(BENCH / "metrics" / "mesh_round_mfu.py")
    r = _reading(work)
    red = r["reduced"]
    want = 100 * 2 * work[0].round_flops(work[1], r["spec"]) / 50e-6 / 197e12
    assert red.window_s == pytest.approx(50e-6)
    assert reader.read(r) == pytest.approx(want)


# ---------------------------------------------------------------------------
# the work count
# ---------------------------------------------------------------------------

def test_work_count_by_hand(work):
    w, cfg = work
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert w.mla_params(cfg) == mla == 13_762_560
    assert w.routed_pairs_per_token(cfg) == 6 * 8 / 128
    moe = 2048 * 128 + 3 * 2048 * 768 * (2 + 6 * 8 / 128)
    per_token = 5 * mla + 3 * 2048 * 6144 + 4 * moe + 16032 * 2048
    assert w.matmul_params_per_token(cfg) == pytest.approx(per_token)
    attn = 5 * 4096 ** 2 * 16 * (128 + 64 + 128)
    fwd = 2 * per_token * 4096 + attn
    spec = {"model": {"per_worker_batch": 1, "seq_len": 4096},
            "algo": {"local_steps": 1}, "data": {"num_workers": 2}}
    # 2 workers x forward + backward, 2 worker evals, 1 global eval
    assert w.round_flops(cfg, spec) == pytest.approx(9 * fwd)
    assert w.round_flops(cfg, spec) == pytest.approx(17.525077e12, rel=1e-6)


def test_work_count_matches_the_program_weights(work):
    """Per token, the work file's MLA and MoE matmuls are the program's
    weights (the held experts at the routed share)."""
    from repro.configs.base import get_arch
    from repro.models.transformer import Transformer
    w, cfg = work
    arch = get_arch(cfg["expect"]["model.name"])
    p = jax.eval_shape(Transformer(arch).init, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda x: x.size // x.shape[0], p["groups"]["b0"])
    assert w.mla_params(cfg) == sum(layer["temporal"][k]
                                    for k in ("wq", "wkva", "wkvb", "wo"))
    m = layer["moe"]
    experts = m["wi"] + m["wu"] + m["wo"]
    share = w.routed_pairs_per_token(cfg) / cfg["n_routed_experts"]
    assert w.moe_params_per_token(cfg) == pytest.approx(
        m["router"] + sum(m["shared"].values()) + experts * share)


# ---------------------------------------------------------------------------
# the output check at a reduced size
# ---------------------------------------------------------------------------

def _reduced_cell():
    ctx = run.load_cell(CELL)
    ctx["cell"]["check"]["limits"] = dict(REDUCED_LIMITS)
    engine = _load(BENCH / "engines" / "mesh_moe.py")
    from repro.configs.base import get_arch
    arch = get_arch("kanana-2-30b-a3b").reduced()
    cfg = dict(ctx["cfg"], **{k: getattr(arch, f)
                              for k, f in engine.ARCH_FIELDS.items()})
    return ctx, cfg


def test_reduced_sound_run_is_correct():
    ctx, cfg = _reduced_cell()
    jax.clear_caches()
    res = run.run(ctx, SEED, 0.5, False, require_chip=False,
                  extra_overrides=REDUCED, cfg=cfg)
    assert res["correct"], res["checks"]


def test_reduced_control_fails_the_limits():
    ctx, cfg = _reduced_cell()
    rows = calibrate.calibrate(ctx, [SEED], 1, require_chip=False,
                               extra_overrides=REDUCED, cfg=cfg)
    control = next(r for r in rows if r["side"] == "control")
    limits = ctx["cell"]["check"]["limits"]
    assert any(control["numbers"][k] > v for k, v in limits.items()), control
