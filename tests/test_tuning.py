"""The VMEM/roofline kernel autotuner (repro.kernels.tuning).

The tuner is pure host-side Python, so these tests pin its contract: chosen
footprints fit the budget, block sizes react to n/dtype/direction, env
overrides win, and the segment default is the ⌈√p⌉ live-tile minimum.
"""

import math

import jax.numpy as jnp
import pytest

from repro.core import butterfly as bf
from repro.kernels import tuning


def test_default_segment_is_ceil_sqrt():
    assert tuning.default_segment(1) == 1
    assert tuning.default_segment(4) == 2
    assert tuning.default_segment(9) == 3
    assert tuning.default_segment(12) == 4
    assert tuning.default_segment(16) == 4
    for p in range(1, 40):
        assert tuning.default_segment(p) == math.ceil(math.sqrt(p))


@pytest.mark.parametrize("kernel", ["butterfly", "sandwich"])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_choice_fits_vmem_budget(kernel, mode):
    for n in (256, 1024, 4096, 8192, 16384):
        c = tuning.tune(kernel, n, "float32", mode)
        # fits the budget, unless already clamped at the sublane floor
        # (weights alone can exceed the model budget at huge n)
        assert (c.vmem_bytes <= tuning.vmem_budget()
                or c.block_b == tuning.MIN_BLOCK_B), c.summary()
        assert tuning.MIN_BLOCK_B <= c.block_b <= tuning.MAX_BLOCK_B
        assert c.block_b & (c.block_b - 1) == 0          # power of two
        assert 1 <= c.segment <= bf.num_stages(n)


def test_block_b_shrinks_with_n_and_backward():
    prev = None
    for n in (256, 1024, 4096, 8192):
        c_fwd = tuning.tune("butterfly", n, "float32", "fwd")
        c_bwd = tuning.tune("butterfly", n, "float32", "bwd")
        # backward keeps ~2·⌈√p⌉ extra tiles live — never a larger tile
        assert c_bwd.block_b <= c_fwd.block_b
        if prev is not None:
            assert c_bwd.block_b <= prev                 # monotone in n
        prev = c_bwd.block_b
    # the hot case from the ISSUE: n=8192 backward cannot run the old flat
    # 256-row default (it would need >80 MB of VMEM)
    assert tuning.tune("butterfly", 8192, "float32", "bwd").block_b < 256


def test_resolve_overrides_beat_env_and_tuner(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_BLOCK_B", "32")
    assert tuning.resolve_block_b("butterfly", 1024, jnp.float32,
                                  "fwd") == 32
    # explicit argument beats the env var
    assert tuning.resolve_block_b("butterfly", 1024, jnp.float32, "fwd",
                                  override=64) == 64
    monkeypatch.setenv("REPRO_TUNE_SEGMENT", "2")
    assert tuning.resolve_segment(12) == 2
    assert tuning.resolve_segment(12, override=3) == 3
    # clamped to [1, stages]
    assert tuning.resolve_segment(4, override=99) == 4
    monkeypatch.delenv("REPRO_TUNE_BLOCK_B")
    monkeypatch.delenv("REPRO_TUNE_SEGMENT")
    # without env/override, the shape-less form falls back to ⌈√p⌉
    assert tuning.resolve_segment(12) == tuning.default_segment(12)


def test_flash_blocks_divide_seq_and_env_override(monkeypatch):
    for s in (64, 1024, 4096, 8192):
        bq, bkv = tuning.flash_blocks(s, 64, "float32", "bwd")
        assert s % bq == 0 and s % bkv == 0
    # env override is read outside the cache: it wins even after the same
    # cell was already queried without it
    monkeypatch.setenv("REPRO_TUNE_BLOCK_Q", "16")
    assert tuning.flash_blocks(1024, 64, "float32") == (16, 16)
    monkeypatch.delenv("REPRO_TUNE_BLOCK_Q")
    assert tuning.flash_blocks(1024, 64, "float32") != (16, 16)


def test_vmem_budget_env_not_stale(monkeypatch):
    """REPRO_TUNE_VMEM_BUDGET set after a first query must still apply
    (the budget is part of the cache key, not trapped under it)."""
    before = tuning.tune("butterfly", 4096, "float32", "bwd").block_b
    monkeypatch.setenv("REPRO_TUNE_VMEM_BUDGET", str(2 * 2 ** 20))
    after = tuning.tune("butterfly", 4096, "float32", "bwd").block_b
    assert after < before
    monkeypatch.delenv("REPRO_TUNE_VMEM_BUDGET")
    assert tuning.tune("butterfly", 4096, "float32", "bwd").block_b == before


def test_tune_registry_and_describe():
    tuning.tune("butterfly", 2048, "bfloat16", "bwd")
    entries = tuning.cache_entries()
    assert any("n2048" in k and "bfloat16" in k for k in entries)
    assert "block_b=" in tuning.describe()


def test_bf16_sublane_floor():
    c = tuning.tune("butterfly", 256, "bfloat16", "fwd")
    assert c.block_b >= 16                               # bf16 min sublane


@pytest.mark.parametrize("n_in,n_out", [(1024, 2048), (2048, 1024),
                                        (1024, 65536)])
@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_fold_follows_width(n_in, n_out, mode):
    """SmolLM-135M's sandwich sites: every MLP side (1024, 2048) and the LM
    head's input side fold their seven in-lane stages onto the MXU; the
    head's 65,536-wide output cannot hold its (128, n) block in VMEM and
    keeps every stage on the VPU. The choice shows in ``describe()`` and
    the MLP sites' modeled footprint fits the budget."""
    c = tuning.tune("sandwich", n_in, "bfloat16", mode, n_out)
    want = tuple(7 if n <= 4096 else 0 for n in (n_in, n_out))
    assert c.folded == want
    assert f"folded={want[0]}/{want[1]}" in tuning.describe()
    if n_out <= 4096:
        assert c.vmem_bytes <= tuning.vmem_budget(), c.summary()
        assert c.segment <= bf.num_stages(max(n_in, n_out)) - 7
    assert tuning.tune("butterfly", n_in, "bfloat16", mode).folded == (7,)
    assert tuning.folded_stages(65536) == 0
    assert [tuning.folded_stages(n) for n in (64, 128, 4096, 8192)] == [
        0, 7, 7, 0]
