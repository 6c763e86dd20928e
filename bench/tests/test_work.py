"""Work counts from shapes, checked by hand."""
import importlib.util
import json

import pytest

from conftest import BENCH


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cnn():
    return (_load(BENCH / "work" / "paper-cnn5-x8.py"),
            json.loads((BENCH / "configs" / "paper-cnn5-x8.json").read_text()))


@pytest.fixture(scope="module")
def smollm():
    return (_load(BENCH / "work" / "smollm-360m.py"),
            json.loads((BENCH / "configs" / "smollm-360m.json").read_text()))


def _paper_spec(compressor="identity", down="identity"):
    return {"data": {"num_workers": 50, "n_local": 512},
            "algo": {"batch_size": 64, "local_epochs": 4},
            "comm": {"compressor": compressor, "downlink_compressor": down}}


def test_cnn_forward_flops_by_hand(cnn):
    work, cfg = cnn
    # conv1 28x28 positions x 3x3x1x8, conv2 14x14 x 3x3x8x16,
    # conv3 7x7 x 3x3x16x16, fc1 784x32, fc2 32x10; 2 FLOPs a MAC
    macs = (28 * 28 * 9 * 1 * 8 + 14 * 14 * 9 * 8 * 16 + 7 * 7 * 9 * 16 * 16
            + 784 * 32 + 32 * 10)
    assert work.forward_flops_per_sample(cfg) == 2 * macs == 841_088
    assert cfg["model"]["forward_flops_per_sample"] == 841_088


def test_cnn_params_match_configuration(cnn):
    work, cfg = cnn
    assert sum(work.param_leaf_sizes(cfg)) == cfg["model"]["params"] == 29_018


def test_cnn_round_flops_counts_required_work_only(cnn):
    work, cfg = cnn
    fwd = 841_088
    train = 3 * fwd * 50 * 512 * 4          # every sample, 4 epochs
    evals = fwd * (50 * 2048 + 2048 + 2048)  # F_{i,t+1}, global, test
    assert work.round_flops(cfg, _paper_spec()) == train + evals
    # the repeated pre-update D_g pass (50 x 2048 forwards) is excluded
    assert work.round_flops(cfg, _paper_spec()) < train + evals + fwd * 50 * 2048


@pytest.mark.parametrize("comp,down,expect", [
    ("identity", "identity", None),
    # per leaf: 50 x (f32 read x2 + f32 residual write + n/2 payload +
    # 4 B scale) uplink, 50 x (n/2 + 4) + 4n aggregate, 10n + 8 downlink
    ("int4", "int8", sum(50 * (12.5 * n + 4) + 50 * (0.5 * n + 4) + 4 * n
                         + (4 * n + n + 4) + (n + 4 + 4 * n)
                         for n in (72, 8, 1152, 16, 2304, 16, 25088, 32,
                                   320, 10))),
])
def test_wire_least_bytes(cnn, comp, down, expect):
    work, cfg = cnn
    assert work.wire_bytes(cfg, _paper_spec(comp, down)) == expect


def test_smollm_matmul_params(smollm):
    work, cfg = smollm
    per_layer = 960 * 64 * (15 + 2 * 5) + 15 * 64 * 960 + 3 * 960 * 2560
    assert work.matmul_params(cfg) == 32 * per_layer + 49152 * 960
    assert round(work.matmul_params(cfg) / 1e6, 1) == 361.8


@pytest.mark.parametrize("seq", [128, 2048])
def test_smollm_causal_attention_term(smollm, seq):
    work, cfg = smollm
    # QK^T and PV over the causal half: 2 products x 2 FLOPs x S^2/2
    assert work.attention_flops_per_sequence(cfg, seq) == 32 * 2 * seq**2 * 960


@pytest.mark.parametrize("seq,batch,tflops", [(2048, 1, 15.655), (128, 2, 1.685)])
def test_smollm_round_flops(smollm, seq, batch, tflops):
    work, cfg = smollm
    spec = {"model": {"seq_len": seq, "per_worker_batch": batch},
            "algo": {"local_steps": 1}, "data": {"num_workers": 2}}
    fwd = (2 * work.matmul_params(cfg) * batch * seq
           + batch * work.attention_flops_per_sequence(cfg, seq))
    assert work.round_flops(cfg, spec) == 3 * fwd * 2 + fwd * 3
    assert round(work.round_flops(cfg, spec) / 1e12, 3) == tflops
