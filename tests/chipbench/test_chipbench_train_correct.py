"""``correct`` of the training cell at smoke size on the CPU: a sound run
passes; runs whose step returns its state unchanged, or trains on half of
each batch, fail; and the float8 control, put in the program's place,
reads above the limits the program stays under."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from chipbench_cells import smoke_cell  # noqa: E402

# smoke-size limits (the chip cell's live in chipbench/limits/): sound
# smoke runs read loss 0.0003-0.0005, gradient 0.010-0.012, change
# 0.005-0.007; the float8 control 0.002-0.004 / 1.1-1.6 / 0.71-0.73 and
# half a batch 0.009-0.030 / 0.41-0.45 / 0.22-0.23
LIMITS = {"loss_gap": 0.005, "grad_norm_gap": 0.1, "update_norm_gap": 0.1}
SEED = 2 ** 33 + 5


def _run(cell):
    from chipbench import run
    return run.run_cell(cell, SEED, 1.0, False, backend="jnp",
                        limit_values=LIMITS)


@pytest.fixture(scope="module")
def cell():
    return smoke_cell("train-2k")


def _patch_step(monkeypatch, wrap):
    from repro.train.trainer import Trainer

    init = Trainer.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        self.step_fn = wrap(self.step_fn)

    monkeypatch.setattr(Trainer, "__init__", patched)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "train_tok_s"}


def test_unchanged_state_is_not_correct(cell, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(inner):
        def step(params, opt_state, batch):
            copy = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
            return params, opt_state, inner(*copy, batch)[2]
        return step

    _patch_step(monkeypatch, wrap)
    res = _run(cell)
    assert not res["correct"]
    assert res["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(cell, monkeypatch):
    def wrap(inner):
        def step(params, opt_state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return inner(params, opt_state, half)
        return step

    _patch_step(monkeypatch, wrap)
    res = _run(cell)
    assert not res["correct"]


def test_float8_control_fails_where_the_program_passes(cell):
    from chipbench import train_driver as td

    cfg, trainer, data, w0 = td.build(cell, SEED, "jnp")
    prog = td.first_steps(trainer, data, w0)
    ref = td.reference_readings(cell, w0, data, 3)
    ctl = td.reference_readings(cell, w0, data, 3, quant="fp8")
    sound, control = td.compare(prog, ref), td.compare(ctl, ref)
    assert td.verdict(sound, 0, LIMITS)[0]
    assert not td.verdict(control, 0, LIMITS)[0]
