"""Substrate: checkpointing, fault tolerance, data pipeline, sharding rules."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint.checkpointing import CheckpointManager
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.runtime import sharding as sh
from repro.runtime.fault_tolerance import (HeartbeatMonitor, StragglerMonitor,
                                           plan_elastic_mesh)
from repro.runtime.pytree import ParamSpec, abstract_params, init_params


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _tree():
    return {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "idx": jnp.arange(3, dtype=jnp.int32)},
            "opt": ({"mu": jnp.ones(4)}, None)}


def test_checkpoint_roundtrip(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    m.save(7, tree, extra={"loss": 1.5})
    s, restored, extra = m.restore(tree)
    assert s == 7 and extra["loss"] == 1.5
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(tree["params"]["w"]))
    assert restored["opt"][1] is None


def test_checkpoint_retention_and_latest(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        m.save(s, _tree())
    assert m.steps() == [3, 4]
    s, _, _ = m.restore(_tree())
    assert s == 4


def test_checkpoint_corruption_fallback(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=5)
    m.save(1, _tree())
    m.save(2, _tree())
    # corrupt the newest
    with open(os.path.join(m._step_dir(2), "arrays.npz"), "wb") as f:
        f.write(b"garbage")
    s, restored, _ = m.restore(_tree())
    assert s == 1 and restored is not None


def test_checkpoint_torn_write_ignored(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=5)
    m.save(1, _tree())
    # simulate a torn write: directory without the sentinel
    os.makedirs(os.path.join(str(tmp_path), "step_000000009"))
    assert m.steps() == [1]


def test_checkpoint_async(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    m.save(5, _tree(), async_=True)
    m.wait()
    assert m.steps() == [5]


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_detects_failure():
    failures = []
    mon = HeartbeatMonitor(["w0", "w1"], timeout=0.15,
                           on_failure=failures.append, poll=0.02)
    try:
        for _ in range(6):
            mon.ping("w0")
            time.sleep(0.05)
        time.sleep(0.2)
        assert "w1" in mon.dead
        assert "w0" in mon.alive or "w0" in mon.dead  # w0 may expire later
        assert "w1" in failures
    finally:
        mon.close()


def test_elastic_mesh_plan_shrinks_data_axis():
    plan = plan_elastic_mesh(alive_devices=192, model_parallelism=16,
                             global_batch=256)
    assert plan.shape == (12, 16) if 256 % 12 == 0 else True
    assert plan.n_devices <= 192
    assert plan.shape[-1] == 16
    assert 256 % plan.shape[0] == 0


def test_elastic_mesh_plan_multipod():
    plan = plan_elastic_mesh(alive_devices=480, model_parallelism=16,
                             global_batch=256, pods=2)
    assert plan.axes == ("pod", "data", "model")
    assert plan.shape[0] == 2 and plan.shape[2] == 16
    assert plan.n_devices <= 480


def test_elastic_mesh_plan_rejects_impossible():
    with pytest.raises(ValueError):
        plan_elastic_mesh(alive_devices=8, model_parallelism=16,
                          global_batch=64)


def test_straggler_monitor_policy():
    mon = StragglerMonitor(["a", "b", "c"], threshold=1.5, patience=3)
    act = mon.record({"a": 1.0, "b": 1.0, "c": 1.0})
    assert act.kind == "none"
    # c becomes slow: first flags → rebalance; persistent → evict
    kinds = []
    for _ in range(4):
        act = mon.record({"a": 1.0, "b": 1.0, "c": 5.0})
        kinds.append(act.kind)
    assert "rebalance" in kinds
    assert kinds[-1] == "evict"
    assert act.worker == "c"


def test_straggler_rebalance_weights_shift_work():
    mon = StragglerMonitor(["a", "b"], threshold=1.2, patience=10)
    act = None
    for _ in range(3):
        act = mon.record({"a": 1.0, "b": 3.0})
    assert act.kind == "rebalance"
    assert act.microbatch_weights["a"] > act.microbatch_weights["b"]


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_per_step():
    cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=4)
    src = SyntheticLM(cfg)
    a = src.batch(5)["tokens"]
    b = src.batch(5)["tokens"]
    np.testing.assert_array_equal(a, b)
    c = src.batch(6)["tokens"]
    assert not np.array_equal(a, c)


def test_data_host_shards_disjoint_streams():
    k = dict(vocab_size=1000, seq_len=32, global_batch=8, host_count=2)
    h0 = SyntheticLM(DataConfig(host_index=0, **k)).batch(0)["tokens"]
    h1 = SyntheticLM(DataConfig(host_index=1, **k)).batch(0)["tokens"]
    assert h0.shape == (4, 32)
    assert not np.array_equal(h0, h1)


def test_prefetcher_ordered_and_resumable():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2)
    src = SyntheticLM(cfg)
    pf = Prefetcher(src, start_step=10)
    try:
        steps = [next(pf)[0] for _ in range(3)]
        assert steps == [10, 11, 12]
    finally:
        pf.close()


def test_data_has_learnable_structure():
    """Motif spans must repeat across batches (models can beat unigram)."""
    cfg = DataConfig(vocab_size=5000, seq_len=128, global_batch=2)
    src = SyntheticLM(cfg)
    toks = np.concatenate([src.batch(i)["tokens"].ravel()
                           for i in range(4)])
    # motif tokens recur far more often than Zipf tail would suggest
    _, counts = np.unique(toks, return_counts=True)
    assert counts.max() > 10


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def test_rules_divisibility_fallback():
    mesh = make_mesh((1,), ("model",))
    # kv_heads = 8 on a 16-way axis must fall back to replication —
    # simulate via a fake mesh dict-driven resolve
    from jax.sharding import PartitionSpec as P
    used = set()
    got = sh.resolve_axis("kv_heads", 8, _FakeMesh({"model": 16}),
                          sh.DEFAULT_RULES, used)
    assert got is None
    got2 = sh.resolve_axis("heads", 96, _FakeMesh({"model": 16}),
                           sh.DEFAULT_RULES, set())
    assert got2 == "model"


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_rules_axis_uniqueness():
    mesh = _FakeMesh({"data": 4, "model": 4})
    spec = sh.logical_to_pspec(("embed", "heads", "head_dim"),
                               (64, 16, 64), mesh, sh.DEFAULT_RULES)
    # "model" must appear at most once across all dims
    axes = [a for part in spec for a in
            ((part,) if isinstance(part, str) else (part or ()))]
    assert axes.count("model") <= 1


def test_batch_axes_composite():
    mesh = _FakeMesh({"pod": 2, "data": 4, "model": 4})
    spec = sh.logical_to_pspec(("batch", None), (64, 128), mesh,
                               sh.DEFAULT_RULES)
    assert spec[0] == ("pod", "data")


def test_butterfly_axes_have_explicit_rules():
    """Every logical axis the butterfly ParamSpecs use resolves through a
    deliberate DEFAULT_RULES entry, not the unknown-name fallback."""
    for name in sh.BUTTERFLY_AXES:
        assert name in sh.DEFAULT_RULES


_MESH_AXES = ("pod", "data", "model")


def _spec_mesh_axes(spec):
    """Flatten a PartitionSpec into the list of mesh-axis names it uses."""
    out = []
    for part in spec:
        if part is None:
            continue
        out.extend((part,) if isinstance(part, str) else part)
    return out


@settings(max_examples=200, deadline=None)
@given(
    mesh_sizes=st.tuples(st.integers(1, 4), st.integers(1, 8),
                         st.integers(1, 4)),
    rules=st.fixed_dictionaries({
        name: st.one_of(
            st.none(),
            st.sampled_from(_MESH_AXES),
            st.lists(st.sampled_from(_MESH_AXES), min_size=1, max_size=3,
                     unique=True).map(tuple))
        # "batch" rides along so the mixed activation case below can
        # actually exercise batch-vs-butterfly mesh-axis competition
        for name in sh.BUTTERFLY_AXES + ("batch",)}),
    stages=st.integers(1, 13),
    n=st.integers(1, 64).map(lambda e: 1 << (e % 14)),
    k_out=st.integers(1, 24),
    k_in=st.integers(1, 24),
)
def test_logical_to_pspec_butterfly_properties(mesh_sizes, rules, stages, n,
                                               k_out, k_in):
    """For ANY rule set over the butterfly logical axes and ANY mesh shape:
    a mesh axis appears at most once per spec, and the mesh-axis product
    assigned to a dim always divides it (replicate instead of mis-shard)."""
    mesh = _FakeMesh(dict(zip(_MESH_AXES, mesh_sizes)))
    cases = [
        (("stages", "butterfly_pair", "butterfly_n"), (stages, 2, n)),
        (("butterfly_core_out", "butterfly_core_in"), (k_out, k_in)),
        (("butterfly_bias",), (n,)),
        # batch + butterfly mix, as in an activation spec
        (("batch", "butterfly_n"), (k_out * 8, n)),
    ]
    for axes, shape in cases:
        spec = sh.logical_to_pspec(axes, shape, mesh, rules)
        used = _spec_mesh_axes(spec)
        # uniqueness: no mesh axis twice in one spec
        assert len(used) == len(set(used)), (spec, rules)
        # all axes exist in the mesh
        assert set(used) <= set(_MESH_AXES)
        # divisibility: assigned product divides the dim (non-divisible
        # dims must have dropped the axes)
        for dim, part in zip(shape, tuple(spec) + (None,) * len(shape)):
            parts = (() if part is None
                     else ((part,) if isinstance(part, str) else part))
            prod = 1
            for a in parts:
                prod *= mesh.shape[a]
            assert dim % prod == 0, (axes, shape, spec, rules)


def test_param_spec_tree_roundtrip():
    specs = {"a": ParamSpec((4, 8), jnp.float32, ("embed", "mlp")),
             "b": [ParamSpec((3,), jnp.float32, (None,), init="zeros")]}
    params = init_params(jax.random.PRNGKey(0), specs)
    assert params["a"].shape == (4, 8)
    assert float(jnp.sum(jnp.abs(params["b"][0]))) == 0.0
    abstract = abstract_params(specs)
    assert abstract["a"].shape == (4, 8)
