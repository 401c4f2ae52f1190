"""Production training entrypoint.

    python -m repro.launch.train --arch smollm-135m-smoke --steps 200 \
        --seq-len 128 --global-batch 8 --checkpoint-dir /tmp/ckpt

On a real TPU deployment this process runs per host under the cluster
launcher; ``jax.distributed.initialize()`` picks up the pod topology and the
same Trainer/step code shards across it (the dry-run proves the production
mesh compiles for every assigned config). On CPU it trains the smoke
variants end-to-end.

Compute/communication overlap: ``--xla-perf-flags`` turns on the TPU
compiler's latency-hiding scheduler and async collectives.
"""

import argparse
import os

_TPU_PERF_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_megacore_fusion_allow_ags=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_async_collective_fusion=true"
)
# libtpu reads its compiler flags from LIBTPU_INIT_ARGS (the CPU runtime
# rejects them in XLA_FLAGS); they are appended to whatever the variable
# already holds, never replacing it.
if "--xla-perf-flags" in os.sys.argv:
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + " " + _TPU_PERF_FLAGS
    ).strip()

# Simulated multi-device CPU run (--simulated-devices N): the host device
# count must reach XLA before jax initializes, hence the pre-import peek
# (mirrors the --xla-perf-flags pattern above; shared with launch/serve.py
# via the jax-free _prejax helper).
from repro.launch._prejax import apply_simulated_devices  # noqa: E402

apply_simulated_devices(os.sys.argv)

import jax  # noqa: E402  (after XLA_FLAGS)
import numpy as np  # noqa: E402


def main():
    # allow_abbrev=False: the pre-import argv peeks above match flags by
    # exact spelling, so abbreviations ('--simulated 8') must not be
    # silently accepted by argparse while missing the peek
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup-steps", type=int, default=100)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--grad-compression", default="",
                    choices=["", "topk", "int8"])
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multihost)")
    ap.add_argument("--xla-perf-flags", action="store_true",
                    help="enable TPU latency-hiding/async-collective flags")
    ap.add_argument("--mesh-shape", default="",
                    help="butterfly data-parallel mesh, e.g. '8' for a "
                         "(data,) mesh or '2x4' for (pod, data); requires "
                         "a butterfly arch (sharded via shard_map)")
    ap.add_argument("--simulated-devices", type=int, default=0,
                    help="force N simulated host devices (CPU; must be >= "
                         "the mesh size). Handled before jax import.")
    args = ap.parse_args()

    if args.distributed:
        jax.distributed.initialize()

    from repro.configs import registry
    from repro.configs.base import TrainConfig
    from repro.train.trainer import Trainer

    cfg = registry.get(args.arch)
    if args.mesh_shape:
        from dataclasses import replace as dc_replace
        if cfg.butterfly is None:
            raise SystemExit(
                f"--mesh-shape needs a butterfly arch (try "
                f"{args.arch}-butterfly); {cfg.name} has no butterfly sites")
        try:
            shape = tuple(int(s) for s in args.mesh_shape.split("x"))
            if not shape or any(s <= 0 for s in shape):
                raise ValueError(shape)
        except ValueError:
            raise SystemExit(
                f"invalid --mesh-shape {args.mesh_shape!r}: expected e.g. "
                f"'8' (data mesh) or '2x4' (pod x data)")
        cfg = cfg.with_(butterfly=dc_replace(cfg.butterfly,
                                             mesh_shape=shape))
    tc = TrainConfig(
        learning_rate=args.lr, warmup_steps=args.warmup_steps,
        total_steps=args.steps, weight_decay=args.weight_decay,
        microbatches=args.microbatches, seed=args.seed,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        grad_compression=args.grad_compression)

    print(f"[train] {cfg.name} | {jax.process_count()} process(es), "
          f"{jax.device_count()} device(s) | steps={args.steps} "
          f"seq={args.seq_len} batch={args.global_batch} "
          f"µb={args.microbatches}")
    trainer = Trainer(cfg, tc, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    result = trainer.run(args.steps)
    print(f"[train] done: loss {np.mean(result.losses[:5]):.4f} → "
          f"{np.mean(result.losses[-5:]):.4f}; "
          f"median step {np.median(result.step_times) * 1e3:.0f} ms"
          # the resolved ExecutionContext of the run (backend, tiles, mesh)
          + f"; exec [{result.execution.describe()}]"
          + (f"; resumed from step {result.resumed_from}"
             if result.resumed_from else ""))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
