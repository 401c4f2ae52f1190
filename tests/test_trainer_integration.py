"""End-to-end trainer integration: loss decreases, grad-accum equivalence,
checkpoint resume, compression path, sharded-butterfly mesh path."""

from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.models import lm
from repro.optim import optimizer as opt
from repro.runtime import pytree as pt
from repro.train import steps as steps_lib
from repro.train.trainer import Trainer


def test_training_reduces_loss(tmp_path):
    cfg = registry.get("smollm-135m-smoke")
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=60,
                     checkpoint_every=0, checkpoint_dir="")
    tr = Trainer(cfg, tc, seq_len=64, global_batch=8)
    res = tr.run(30)
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5]) - 0.3


def test_step_phases_and_program_name(tmp_path):
    """Each step's host work is three profiler phases in order — batch,
    dispatch, read-back — and the step's device program is
    ``jit_train_step``."""
    import glob

    from jax.profiler import ProfileData

    cfg = registry.get("smollm-135m-smoke")
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=1, total_steps=4,
                     checkpoint_every=0, checkpoint_dir="")
    tr = Trainer(cfg, tc, seq_len=16, global_batch=2)
    tr.run(1)                       # compile outside the profiled steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = tr.run(2, params=tr.params, opt_state=tr.opt_state)
    finally:
        jax.profiler.stop_trace()
    assert len(res.losses) == 2
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    evs = sorted((int(ev.start_ns), int(ev.duration_ns), ev.name)
                 for p in ProfileData.from_file(path).planes
                 for line in p.lines for ev in line.events
                 if ev.name.startswith("train."))
    assert [n for _, _, n in evs] == [
        "train.batch", "train.dispatch", "train.readback"] * 2
    for (s0, d0, _), (s1, _, _) in zip(evs, evs[1:]):
        assert s0 + d0 <= s1
    batch = tr._make_batch_arrays(tr.data.batch(0))
    assert "module @jit_train_step" in tr.step_fn.lower(
        tr.params, tr.opt_state, batch).as_text()


def test_checkpoint_resume_continues(tmp_path):
    cfg = registry.get("smollm-135m-smoke")
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=60,
                     checkpoint_every=10, checkpoint_dir=str(tmp_path))
    Trainer(cfg, tc, seq_len=64, global_batch=8).run(12)
    res2 = Trainer(cfg, tc, seq_len=64, global_batch=8).run(3)
    assert res2.resumed_from == 10


def test_grad_accumulation_equivalence():
    """k microbatches must produce the same update as one big batch."""
    cfg = registry.get("smollm-135m-smoke").with_(compute_dtype="float32")
    params = pt.init_params(jax.random.PRNGKey(0), lm.model_specs(cfg))
    tx = opt.sgd(0.1)
    rng = np.random.default_rng(0)
    B, S = 4, 32
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                               jnp.int32),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    step1 = steps_lib.make_train_step(cfg, tx, microbatches=1)
    step4 = steps_lib.make_train_step(cfg, tx, microbatches=4)
    p1, _, m1 = step1(params, tx.init(params), batch)
    p4, _, m4 = step4(params, tx.init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    flat1 = jax.tree_util.tree_leaves(p1)
    flat4 = jax.tree_util.tree_leaves(p4)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_sharded_butterfly_training_on_8_devices():
    """ButterflyConfig.mesh_shape=(8,) routes every butterfly site through
    the shard_map wrappers on the simulated 8-device mesh (conftest): the
    run must report the layout, train to finite loss, and the loss curve
    must track the unsharded run (same data, same init; float32 compute so
    only reduction-order noise separates the two)."""
    assert jax.device_count() >= 8
    cfg = registry.get("smollm-135m-butterfly-smoke").with_(
        compute_dtype="float32")
    cfg_sh = cfg.with_(butterfly=dc_replace(cfg.butterfly, mesh_shape=(8,)))
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=20,
                     checkpoint_every=0)
    res_sh = Trainer(cfg_sh, tc, seq_len=32, global_batch=8).run(4)
    assert res_sh.mesh_layout == "data=8"
    assert np.all(np.isfinite(res_sh.losses))
    res_1d = Trainer(cfg, tc, seq_len=32, global_batch=8).run(4)
    assert res_1d.mesh_layout == ""
    np.testing.assert_allclose(res_sh.losses[0], res_1d.losses[0],
                               rtol=1e-4)
    np.testing.assert_allclose(res_sh.losses, res_1d.losses, rtol=5e-3,
                               atol=1e-4)


def test_compressed_training_still_learns():
    cfg = registry.get("smollm-135m-smoke")
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=60,
                     grad_compression="topk", grad_compression_ratio=0.2)
    tr = Trainer(cfg, tc, seq_len=64, global_batch=8)
    res = tr.run(30)
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5])
