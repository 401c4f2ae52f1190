import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-compile every (arch × shape × mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun``): the
XLA_FLAGS line above executes before any jax import so 512 host devices are
available for the production meshes (16x16 single-pod, 2x16x16 multi-pod).

Per cell:
  * build abstract inputs (ShapeDtypeStruct only — no allocation),
  * ``jax.jit(step, in_shardings, out_shardings, donate).lower().compile()``,
  * print ``memory_analysis()`` (proves HBM fit) and ``cost_analysis()``,
  * derive the three roofline terms (repro.launch.roofline) and write
    ``<out>/<arch>__<shape>__<mesh>.json``.

Any sharding mismatch / compile OOM / unsupported collective here is a bug
in the framework, not an environment problem.
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import (SHAPES, SHAPES_BY_NAME, ModelConfig,
                                ShapeConfig, cell_applicable)
from repro.launch import roofline as rl
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.runtime import sharding as sh
from repro.train import steps as steps_lib


def _tree_shardings_like(tree, leaf_sharding):
    return jax.tree_util.tree_map(lambda _: leaf_sharding, tree)


def make_dryrun_train_step(cfg: ModelConfig, microbatches: int):
    """Explicit-state AdamW train step (params, mu, nu, count, batch):
    state trees mirror the param tree so sharding trees are trivial."""
    b1, b2, eps, lr, wd = 0.9, 0.999, 1e-8, 3e-4, 0.1

    def loss_fn(params, mb):
        return lm.loss_fn(cfg, params, mb)

    def step(params, mu, nu, count, batch):
        if microbatches == 1:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mbs = jax.tree_util.tree_map(split, batch)
            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def mb_step(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g)
                return (gsum, lsum + l), None

            (grads, lsum), _ = jax.lax.scan(mb_step, (zero, 0.0), mbs)
            grads = jax.tree_util.tree_map(
                lambda g: g / microbatches, grads)
            loss = lsum / microbatches
        # global-norm clip
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, 1.0 / (gnorm + 1e-9))
        count = count + 1
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def upd(p, m, v, g):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if p.ndim > 1:
                u = u + wd * p.astype(u.dtype)
            return (p - lr * u.astype(p.dtype)).astype(p.dtype), m, v

        out = jax.tree_util.tree_map(upd, params, mu, nu, grads)
        params = jax.tree_util.tree_map(lambda t: t[0], out,
                                        is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree_util.tree_map(lambda t: t[1], out,
                                    is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree_util.tree_map(lambda t: t[2], out,
                                    is_leaf=lambda x: isinstance(x, tuple))
        return params, mu, nu, count, loss

    return step


def _fits(compiled, hbm: float = 16e9) -> bool:
    ma = compiled.memory_analysis()
    tot = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
           + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return tot <= hbm


def choose_microbatches(cfg: ModelConfig, shape: ShapeConfig,
                        n_dp: int) -> int:
    """Bound per-microbatch tokens so activations fit: ~4k tokens/µb for
    wide models, ~8k otherwise (measured: unmicrobatched 64k-token steps
    blow HBM on every arch via attention/logit buffers)."""
    local_batch = max(1, shape.global_batch // n_dp)
    target_tokens = 4096 if cfg.d_model >= 1024 else 8192
    seqs_per_mb = max(1, target_tokens // shape.seq_len)
    return max(1, local_batch // seqs_per_mb)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules=None, out_dir: Optional[str] = None,
             verbose: bool = True) -> Dict:
    cfg = registry.get(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, reason = cell_applicable(cfg, shape)
    result: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        result["status"] = "skipped"
        result["reason"] = reason
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = int(np.prod(list(mesh.shape.values())))
    rules = dict(sh.DEFAULT_RULES if rules is None else rules)
    pod_boundary = 256 if multi_pod else 0
    n_dp = (mesh.shape.get("pod", 1) * mesh.shape["data"])

    t0 = time.time()
    with sh.use_sharding(mesh, rules):
        pshard = sp.param_shardings(cfg, mesh, rules)
        pabs = sp.abstract_model(cfg)
        if shape.kind == "train":
            mb = choose_microbatches(cfg, shape, n_dp)
            step = make_dryrun_train_step(cfg, mb)
            bshard = sp.batch_shardings(cfg, shape, mesh, rules)
            babs = sp.batch_specs(cfg, shape)
            count = jax.ShapeDtypeStruct((), jnp.int32)
            rep = sp.replicated(mesh)
            fn = jax.jit(
                step,
                in_shardings=(pshard, pshard, pshard, rep, bshard),
                donate_argnums=(0, 1, 2, 3))
            with mesh:
                lowered = fn.lower(pabs, pabs, pabs, count, babs)
                compiled = lowered.compile()
            result["microbatches"] = mb
        elif shape.kind == "prefill":
            # NOTE: batch-chunked prefill (make_prefill_step(chunks=2)) is
            # only profitable when the chunk boundary aligns with the DP
            # sharding — slicing a batch-sharded cache makes GSPMD gather
            # the full stack (measured 800+GB temp). Single-step prefill is
            # the production default here; see EXPERIMENTS.md §Perf iter 3.
            result["prefill_chunks"] = 1
            step = steps_lib.make_prefill_step(cfg, 1)
            bshard = sp.batch_shardings(cfg, shape, mesh, rules)
            babs = sp.batch_specs(cfg, shape)
            cshard = sp.cache_shardings(cfg, shape, mesh, rules)
            cabs = lm.cache_specs(cfg, shape.global_batch, shape.seq_len)
            fn = jax.jit(step, in_shardings=(pshard, bshard, cshard),
                         donate_argnums=(2,))
            with mesh:
                lowered = fn.lower(pabs, babs, cabs)
                compiled = lowered.compile()
        else:  # decode
            step = steps_lib.make_serve_step(cfg)
            token, cabs, cur = sp.decode_specs(cfg, shape)
            cshard = sp.cache_shardings(cfg, shape, mesh, rules)
            tshard = NamedSharding(mesh, sh.logical_to_pspec(
                ("batch",), token.shape, mesh, rules))
            rep = sp.replicated(mesh)
            fn = jax.jit(step, in_shardings=(pshard, tshard, cshard, rep),
                         donate_argnums=(2,))
            with mesh:
                lowered = fn.lower(pabs, token, cabs, cur)
                compiled = lowered.compile()

        if shape.kind == "prefill" and not _fits(compiled) \
                and shape.global_batch % (2 * n_dp) == 0:
            # production serving splits oversized prefill batches across
            # sequential engine calls; lower the half-batch step and record
            # it (the roofline terms below are per call — 2 calls/batch)
            shape = ShapeConfig(shape.name, shape.seq_len,
                                shape.global_batch // 2, shape.kind)
            result["batch_split"] = 2
            bshard = sp.batch_shardings(cfg, shape, mesh, rules)
            babs = sp.batch_specs(cfg, shape)
            cshard = sp.cache_shardings(cfg, shape, mesh, rules)
            cabs = lm.cache_specs(cfg, shape.global_batch, shape.seq_len)
            fn = jax.jit(steps_lib.make_prefill_step(cfg, 1),
                         in_shardings=(pshard, bshard, cshard),
                         donate_argnums=(2,))
            with mesh:
                compiled = fn.lower(pabs, babs, cabs).compile()

    compile_s = time.time() - t0
    mf, tokens = sp.model_flops(cfg, shape, n_devices)
    total, active = sp.param_counts(cfg)
    report = rl.build_report(
        arch, shape_name, mesh_name, n_devices, compiled,
        pod_boundary=pod_boundary, model_flops=mf,
        params_total=total, params_active=active, tokens=tokens)
    result.update(report.to_dict())
    result["status"] = "ok"
    result["compile_seconds"] = round(compile_s, 2)
    ma = compiled.memory_analysis()
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name}] compiled in "
              f"{compile_s:.1f}s")
        print(f"  memory_analysis: {ma}")
        ca = compiled.cost_analysis()
        print(f"  cost: flops/dev={ca.get('flops', 0):.3e} "
              f"bytes/dev={ca.get('bytes accessed', 0):.3e}")
        print(f"  roofline: compute={report.t_compute*1e3:.2f}ms "
              f"memory={report.t_memory*1e3:.2f}ms "
              f"collective={report.t_collective*1e3:.2f}ms "
              f"dominant={report.dominant} "
              f"util={report.flops_utilization:.2f} "
              f"fit={report.hbm_fit}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{arch}__{shape_name}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--stop-on-error", action="store_true")
    args = ap.parse_args()

    archs = registry.names() if args.arch == "all" else args.arch.split(",")
    shapes = ([s.name for s in SHAPES] if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                try:
                    results.append(run_cell(arch, shape, multi,
                                            out_dir=args.out))
                except Exception as e:
                    failures += 1
                    print(f"[FAIL {arch} × {shape} × "
                          f"{'multi' if multi else 'single'}]: {e}")
                    traceback.print_exc(limit=4)
                    if args.stop_on_error:
                        raise
    ok = sum(1 for r in results if r.get("status") == "ok")
    skipped = sum(1 for r in results if r.get("status") == "skipped")
    print(f"\n=== dry-run: {ok} compiled, {skipped} skipped, "
          f"{failures} failed ===")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
