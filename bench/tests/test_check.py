"""The output check at a CPU size: a sound run is correct; the control
(the reference in the precision below the configuration's) and every
fault planted under the timed path are not.

These drive `run.run` / `calibrate.calibrate` past the look for a chip,
with the cell's own limits."""
import jax
import jax.numpy as jnp
import pytest

import calibrate
import run
from conftest import tiny

CELLS = ("paper-cnn5-x8.c50-dense", "paper-cnn5-x8.c50-int4")
SEED = 2**31 + 17


def _run(cell, wrap_step=None):
    ctx, cfg, ov = tiny(cell)
    jax.clear_caches()
    return run.run(ctx, SEED, 0.5, False, require_chip=False,
                   extra_overrides=ov, cfg=cfg, wrap_step=wrap_step)


def _failed(res):
    return [k for k, c in res["checks"].items()
            if c["limit"] is not None and not (c["value"] <= c["limit"])]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    ctx, cfg, ov = tiny(cell)
    rows = calibrate.calibrate(ctx, [SEED], 1, require_chip=False,
                               extra_overrides=ov, cfg=cfg)
    control = next(r for r in rows if r["side"] == "control")
    limits = ctx["cell"]["check"]["limits"]
    assert any(control["numbers"][k] > v for k, v in limits.items()), control


def _unchanged(prep):
    """The step returns the state it was given."""
    step = prep.step

    def broken(state, key):
        _, tel, key = step(jax.tree.map(jnp.copy, state), key)
        return state, tel, key
    return prep._replace(step=broken)


def _half_batch(monkeypatch):
    from repro.core import mdsl, rounds
    sgd = mdsl._local_sgd_epochs
    monkeypatch.setattr(mdsl, "_local_sgd_epochs",
                        lambda p, x, y, *a, **k: sgd(p, x[: x.shape[0] // 2],
                                                     y[: y.shape[0] // 2],
                                                     *a, **k))
    grad = rounds.accumulated_grad
    monkeypatch.setattr(rounds, "accumulated_grad",
                        lambda f, p, batch, m: grad(f, p, jax.tree.map(
                            lambda x: x[: max(1, x.shape[0] // 2)], batch), m))


def _no_exchange(monkeypatch):
    from repro.core import rounds
    wire = rounds.wire_round

    def broken(comm, **kw):
        return wire(comm, **kw)._replace(global_params=kw["global_params"])
    monkeypatch.setattr(rounds, "wire_round", broken)


def _flip_upload(monkeypatch):
    from repro.comm import channel

    def broken(cfg, prev, new, key):
        def leaf(p, n):
            first = (jnp.arange(n.shape[0]) == 0).reshape(
                (-1,) + (1,) * (n.ndim - 1))
            return jnp.where(first, 2 * p - n, n).astype(n.dtype)
        return jax.tree.map(leaf, prev, new)
    monkeypatch.setattr(channel, "corrupt_local_updates", broken)


def _invert_select(monkeypatch):
    from repro.core import selection
    pick = selection.select_workers

    def broken(theta, state):
        mask, sel = pick(theta, state)
        return 1.0 - mask, sel
    monkeypatch.setattr(selection, "select_workers", broken)


def _frozen_residual(monkeypatch):
    from repro.comm import compress
    monkeypatch.setattr(compress, "select_residual",
                        lambda mask, new, old: old)


BREAKS = {"half_batch": _half_batch, "no_exchange": _no_exchange,
          "flip_upload": _flip_upload, "invert_select": _invert_select,
          "frozen_residual": _frozen_residual}
FAULTS = [(c, f) for c in CELLS for f in ("unchanged", *BREAKS)
          # only the int4 cell carries an error-feedback residual
          if f != "frozen_residual" or c.endswith("int4")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_makes_run_incorrect(cell, fault, monkeypatch):
    wrap = None
    if fault == "unchanged":
        wrap = _unchanged
    else:
        BREAKS[fault](monkeypatch)
    res = _run(cell, wrap_step=wrap)
    assert not res["correct"]
    assert _failed(res), res["checks"]
