"""Dry-run machinery: HLO analyzer correctness, cell applicability, and a
real (subprocess) mini dry-run on the production mesh.

The subprocess is required because XLA_FLAGS=--xla_force_host_platform_
device_count must be set before jax initializes — tests in this process see
a single device (assignment requirement: never set it globally)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES_BY_NAME, cell_applicable
from repro.configs import registry
from repro.launch import hlo_analysis as ha
from repro.launch import roofline as rl

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_analyzer_counts_scan_trip_multiplicity():
    def body(c, x):
        return c @ x, ()

    def f(c, xs):
        return jax.lax.scan(body, c, xs)[0]

    c = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    xs = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(c, xs).compile()
    # cost_analysis undercounts (counts the body once) ...
    assert compiled.cost_analysis()["flops"] < 12 * 2 * 64**3 / 2
    # ... the loop-aware analyzer does not
    cost = ha.analyze(compiled.as_text())
    np.testing.assert_allclose(cost.flops, 12 * 2 * 64**3, rtol=0.05)
    assert any(t == 12 for _, t in cost.loops)


def test_analyzer_matmul_exact():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    b = jax.ShapeDtypeStruct((1024, 256), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    cost = ha.analyze(compiled.as_text())
    np.testing.assert_allclose(cost.flops, 2 * 512 * 1024 * 256, rtol=0.02)


def test_analyzer_collective_classification():
    text = """
HloModule test

ENTRY %main (p: f32[128]) -> f32[128] {
  %p = f32[128]{0} parameter(0)
  %ar = f32[128]{0} all-reduce(%p), channel_id=1, replica_groups=[16,16]<=[256], use_global_device_ids=true, to_apply=%add
  ROOT %ar2 = f32[128]{0} all-reduce(%ar), channel_id=2, replica_groups=[1,512]<=[512], use_global_device_ids=true, to_apply=%add
}
"""
    cost = ha.analyze(text, pod_boundary=256)
    assert cost.collective_counts.get("all-reduce") == 2
    assert cost.collective_dcn > 0 and cost.collective_ici > 0


def test_cell_applicability_matrix():
    long = SHAPES_BY_NAME["long_500k"]
    ok, _ = cell_applicable(registry.get("recurrentgemma-2b"), long)
    assert ok
    ok, reason = cell_applicable(registry.get("mistral-large-123b"), long)
    assert not ok and "full-attention" in reason
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        for arch in registry.names():
            ok, _ = cell_applicable(registry.get(arch),
                                    SHAPES_BY_NAME[shape])
            assert ok


@pytest.mark.slow
def test_mini_dryrun_subprocess_production_mesh():
    """Full dry-run path for one real cell on the 16x16 production mesh —
    proves lower+compile+roofline works end-to-end on 256 fake devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "smollm-135m", "--shape", "decode_32k", "--mesh", "both",
         "--out", "/tmp/test_dryrun_cell"],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    with open("/tmp/test_dryrun_cell/"
              "smollm-135m__decode_32k__pod16x16.json") as f:
        rec = json.load(f)
    assert rec["status"] == "ok"
    assert rec["hbm_fit"] is True
    assert rec["flops_per_device"] > 0
    assert rec["t_memory"] > 0
    with open("/tmp/test_dryrun_cell/"
              "smollm-135m__decode_32k__pod2x16x16.json") as f:
        rec2 = json.load(f)
    assert rec2["status"] == "ok" and rec2["n_devices"] == 512
