"""Training loop: prefetched data, checkpoint/resume, straggler accounting.

The Trainer is deliberately host-side thin: all math lives in the jitted
step function (``jit_train_step`` in a profile); the loop does data,
checkpoints, failure handling, logging. Each step's host work is three
``jax.profiler`` phases (:meth:`repro.obs.Tracer.phase`):
``train.batch`` (the next batch, placed on the devices),
``train.dispatch`` (the step's call) and ``train.readback`` (its loss
read back, which waits for the device).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint.checkpointing import CheckpointManager
from repro.configs.base import ModelConfig, TrainConfig
from repro.data.pipeline import Prefetcher, SyntheticLM, for_model
from repro.kernels import context as exctx
from repro.kernels import tuning
from repro.models import lm
from repro.obs.tracing import NULL_TRACER
from repro.optim import optimizer as opt
from repro.runtime import pytree as pt
from repro.runtime import sharding as rsh
from repro.runtime.fault_tolerance import StragglerMonitor
from repro.train import steps as steps_lib


@dataclass(frozen=True)
class ExecutionRecord:
    """How the butterfly sites of a run actually executed.

    * ``context`` — the finalized :class:`ExecutionContext` the step
      function traced under (``None`` when the model has no butterfly
      sites).
    * ``backend`` — its resolved kernel backend ("dense" without butterfly
      sites).
    * ``tuning`` — autotuner decisions (block_b/segment per kernel cell)
      registered while this run traced; falls back to the process-wide
      registry (prefixed "process-wide:") when tracing hit a warm cache
      from an earlier run in the same process. Empty on jnp/dense paths.
    * ``mesh_layout`` — e.g. "data=8" or "pod=2,data=4"; "" single-device.
    """

    backend: str = "dense"
    tuning: str = ""
    mesh_layout: str = ""
    context: Optional[exctx.ExecutionContext] = None

    def describe(self) -> str:
        return (self.context.describe() if self.context is not None
                else "dense")


@dataclass
class TrainResult:
    steps_run: int
    losses: List[float]
    resumed_from: Optional[int]
    step_times: List[float] = field(default_factory=list)
    # the resolved execution policy of the run (supersedes the old
    # kernel_backend / kernel_tuning / mesh_layout fields, which live on as
    # read-only aliases below)
    execution: ExecutionRecord = field(default_factory=ExecutionRecord)

    @property
    def kernel_backend(self) -> str:
        """Alias for ``execution.backend`` (pre-ExecutionContext name)."""
        return self.execution.backend

    @property
    def kernel_tuning(self) -> str:
        """Alias for ``execution.tuning`` (pre-ExecutionContext name)."""
        return self.execution.tuning

    @property
    def mesh_layout(self) -> str:
        """Alias for ``execution.mesh_layout`` (pre-ExecutionContext name)."""
        return self.execution.mesh_layout


class Trainer:
    def __init__(self, model_cfg: ModelConfig, train_cfg: TrainConfig,
                 seq_len: int, global_batch: int,
                 data: Optional[SyntheticLM] = None):
        self.cfg = model_cfg
        self.tc = train_cfg
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.data = data or for_model(model_cfg, seq_len, global_batch,
                                      seed=train_cfg.seed)
        self.tx = steps_lib.make_optimizer(train_cfg)
        # Resolve the run's ExecutionContext up front — concrete backend and
        # a built mesh — and freeze it: the step function traces inside
        # `use_execution(self.exec_ctx)`, so "auto" can't re-resolve
        # differently at trace time and diverge from what TrainResult
        # reports. The train step differentiates through the sandwich, and
        # since the fused Pallas kernels carry custom_vjp backward passes
        # the fused path is safe to trace under grad — "auto" keeps it on
        # TPU end to end. Mesh construction (ButterflyConfig.mesh_shape
        # opts in) fails loudly here — with the XLA_FLAGS recipe in the
        # message — rather than mid-trace.
        bc = model_cfg.butterfly
        if bc is not None:
            self.exec_ctx = exctx.resolve_execution(
                default=exctx.ExecutionContext.from_butterfly_config(bc))
            self.kernel_backend = self.exec_ctx.backend
            self.mesh = self.exec_ctx.mesh
        else:
            self.exec_ctx = None
            self.kernel_backend = "dense"
            self.mesh = None
        self.step_fn = jax.jit(steps_lib.make_train_step(
            model_cfg, self.tx, train_cfg.microbatches),
            donate_argnums=(0, 1))
        self.ckpt = (CheckpointManager(train_cfg.checkpoint_dir,
                                       keep=train_cfg.keep_checkpoints)
                     if train_cfg.checkpoint_dir else None)

    def init_state(self, seed: int = 0):
        specs = lm.model_specs(self.cfg)
        params = pt.init_params(jax.random.PRNGKey(seed), specs)
        opt_state = self.tx.init(params)
        return params, opt_state

    def _sharding_scope(self):
        """Ambient contexts for trace/execution: the run's ExecutionContext
        (so every butterfly site sees the frozen policy) plus the sharding
        context when a mesh is configured; no-op for dense models."""
        stack = contextlib.ExitStack()
        if self.exec_ctx is not None:
            stack.enter_context(exctx.use_execution(self.exec_ctx))
        if self.mesh is not None:
            stack.enter_context(rsh.use_sharding(self.mesh))
        return stack

    def _mesh_layout(self) -> str:
        return self.exec_ctx.mesh_layout() if self.exec_ctx else ""

    def _put_batch(self, x: jnp.ndarray) -> jnp.ndarray:
        """Place a (batch, ...) array batch-sharded on the mesh's data axes
        (replicate when the batch doesn't divide them)."""
        spec = rsh.batch_axes(self.mesh, rsh.DEFAULT_RULES, x.shape[0])
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def _make_batch_arrays(self, batch: Dict[str, np.ndarray]
                           ) -> Dict[str, jnp.ndarray]:
        out = {k: jnp.asarray(v) for k, v in batch.items()}
        B = out["tokens"].shape[0]
        cfg = self.cfg
        rng = np.random.default_rng(1234)
        if cfg.frontend == "vision":
            out["frontend_embeds"] = jnp.asarray(rng.normal(
                size=(B, cfg.frontend_tokens, cfg.d_model)), jnp.float32)
        if cfg.n_enc_layers:
            out["frames"] = jnp.asarray(rng.normal(
                size=(B, cfg.enc_seq, cfg.d_model)), jnp.float32)
        if self.mesh is not None:
            out = {k: self._put_batch(v) for k, v in out.items()}
        return out

    def run(self, steps: int, params=None, opt_state=None,
            resume: bool = True) -> TrainResult:
        if params is None:
            params, opt_state = self.init_state(self.tc.seed)

        start_step = 0
        resumed_from = None
        if self.ckpt is not None and resume:
            tmpl = {"params": params, "opt": opt_state}
            s, tree, extra = self.ckpt.restore(tmpl)
            if s is not None:
                params = jax.tree_util.tree_map(
                    lambda t, a: jnp.asarray(a) if a is not None else t,
                    tmpl["params"], tree["params"],
                    is_leaf=lambda x: x is None)
                opt_state = jax.tree_util.tree_map(
                    lambda t, a: (jnp.asarray(a) if a is not None else None),
                    tmpl["opt"], tree["opt"], is_leaf=lambda x: x is None)
                start_step = s
                resumed_from = s

        tuning_before = set(tuning.cache_entries())
        prefetch = Prefetcher(self.data, start_step=start_step)
        straggler = StragglerMonitor(["host0"])
        losses: List[float] = []
        step_times: List[float] = []
        try:
            for i in range(start_step, start_step + steps):
                with NULL_TRACER.phase("train.batch"):
                    step_idx, raw = next(prefetch)
                    batch = self._make_batch_arrays(raw)
                t0 = time.monotonic()
                # the sharding ctx must be live whenever the step function
                # (re)traces — butterfly sites read the active mesh then
                with self._sharding_scope(), \
                        NULL_TRACER.phase("train.dispatch"):
                    params, opt_state, metrics = self.step_fn(
                        params, opt_state, batch)
                with NULL_TRACER.phase("train.readback"):
                    loss = float(metrics["loss"])
                dt = time.monotonic() - t0
                straggler.record({"host0": dt})
                losses.append(loss)
                step_times.append(dt)
                if (self.ckpt is not None and self.tc.checkpoint_every
                        and (i + 1) % self.tc.checkpoint_every == 0):
                    self.ckpt.save(i + 1, {"params": params,
                                           "opt": opt_state},
                                   extra={"loss": loss}, async_=True)
        finally:
            prefetch.close()
            if self.ckpt is not None:
                self.ckpt.wait()
        self.params = params
        self.opt_state = opt_state
        # Tuning choices are made (and registered) at trace time. Report the
        # entries this run added; if tracing hit a warm registry (another
        # run with the same cells already happened in this process), fall
        # back to the full registry, marked as such. jnp/dense paths never
        # query the autotuner and report "".
        tuning_summary = ""
        if self.kernel_backend in ("pallas", "pallas_interpret"):
            entries = tuning.cache_entries()
            fresh = sorted(v for k, v in entries.items()
                           if k not in tuning_before)
            if fresh:
                tuning_summary = "; ".join(fresh)
            elif entries:
                tuning_summary = "process-wide: " + tuning.describe()
        return TrainResult(steps_run=steps, losses=losses,
                           resumed_from=resumed_from,
                           step_times=step_times,
                           execution=ExecutionRecord(
                               backend=self.kernel_backend,
                               tuning=tuning_summary,
                               mesh_layout=self._mesh_layout(),
                               context=self.exec_ctx))
