"""The training driver: the program's ``Trainer`` at the mix's sequence
length and batch, fed seeded random token ids, every row different.

Set-up builds one trainer with the benchmark's weights and drives it
through its first three steps through ``Trainer.run`` (the call and feed
the window uses; the first step compiles). Those three steps are what the
reference follows: each step's loss, the first gradient as the optimizer
receives it (Adam's first moment after one step, over ``1 - beta1``) and
each weight's change after the three. The same trainer then runs whole
steps until ``seconds`` have passed.
"""

from __future__ import annotations

import functools
import gc
import statistics
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import reference, weights

BETA1 = 0.9


class Batches:
    """The trainer's feed: ``batch(step)`` is seeded random ids for the
    ``offset + step``-th batch; the driver moves ``offset`` on after each
    call, so every step trains on new rows."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.B, self.S, self.V = seed, batch, seq, vocab
        self.offset = 0

    def tokens(self, i: int) -> np.ndarray:
        rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, 7, i])
        return rng.integers(0, self.V, size=(self.B, self.S),
                            dtype=np.int32)

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        t = self.tokens(self.offset + step)
        return {"tokens": t, "targets": t.copy(),
                "mask": np.ones(t.shape, np.float32)}


@functools.lru_cache(maxsize=None)
def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=0)
    def leaf_norms(stacked, leaves, base):
        out = []
        for i, (s, a) in enumerate(zip(stacked, leaves)):
            a = a.astype(jnp.float32)
            if base is not None:
                a = a - base[i].astype(jnp.float32)
            axes = tuple(range(1, a.ndim)) if s else None
            out.append(jnp.sqrt(jnp.sum(a * a, axis=axes)))
        return out
    return leaf_norms


def _norms(tree, base=None) -> List[float]:
    """Norm of every leaf of ``tree`` (less ``base``'s, when given), and of
    every layer of a leaf stacked over the layer scan, in a fixed order;
    one jitted call, not a few eager operations per leaf."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    stacked = tuple(any(getattr(p, "key", None) == "unit" for p in path)
                    for path, _ in flat)
    base = None if base is None else jax.tree_util.tree_leaves(base)
    got = _leaf_norms_fn()(stacked, [a for _, a in flat], base)
    return [float(x) for n in got for x in np.asarray(n).reshape(-1)]


def _adam_mu(opt_state):
    """Adam's first moment in the trainer's chained optimizer state."""
    for part in opt_state:
        if hasattr(part, "mu") and hasattr(part, "nu"):
            return part.mu
    raise ValueError("no Adam state in the optimizer state")


def worst_leaf(prog: List[float], ref: List[float], keep: List[bool]
               ) -> float:
    """The largest gap between the program's and the reference's norm of a
    leaf, against that leaf's reference norm or the median leaf's,
    whichever is larger."""
    kept = [r for r, k in zip(ref, keep) if k]
    med = statistics.median(kept)
    return max(abs(p - r) / max(r, med)
               for p, r, k in zip(prog, ref, keep) if k)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three numbers compared: the worst step's relative loss gap, the
    worst leaf's first-gradient norm gap and the worst leaf's change-norm
    gap. Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out."""
    med = statistics.median(ref["grad"])
    keep = [g >= 1e-3 * med for g in ref["grad"]]
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss,
            "grad_norm_gap": worst_leaf(prog["grad"], ref["grad"], keep),
            "update_norm_gap": worst_leaf(prog["update"], ref["update"],
                                          keep),
            "leaves_left_out": float(len(keep) - sum(keep))}


def verdict(got: Dict[str, float], window_compiles: int,
            limits: Dict[str, float]):
    """``(correct, checks)``: each number compared beside its limit (a
    number without a limit gets -1, which no reading meets)."""
    checks = {k: (v, limits.get(k, -1.0)) for k, v in got.items()
              if k != "leaves_left_out"}
    checks["window_compiles"] = (window_compiles, 0.0)
    return all(v <= l for v, l in checks.values()), checks


def reference_readings(cell, w0, data: Batches, steps: int,
                       quant: Optional[str] = None,
                       rows: Optional[int] = None) -> Dict:
    """The reference's losses, first-gradient norms and change norms over
    the program's first ``steps`` batches (``rows`` keeps only the first
    rows of each: the half-batch fault)."""
    tr = cell.traffic["trainer"]
    batches = [data.tokens(i)[:rows] for i in range(steps)]
    losses, g, w = reference.train_steps(cell.config, w0, batches, tr,
                                         quant=quant)
    return {"losses": losses, "grad": _norms(g),
            "update": _norms(w, w0)}


def build(cell, seed: int, backend: Optional[str] = None):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.models import lm
    from repro.runtime import pytree as pt
    from repro.train.trainer import Trainer
    from chipbench import manifest

    cfg = manifest.model_config(cell.config, backend)
    tr = cell.traffic["trainer"]
    tc = TrainConfig(learning_rate=tr["learning_rate"],
                     warmup_steps=tr["warmup_steps"],
                     total_steps=tr["total_steps"],
                     weight_decay=tr["weight_decay"],
                     max_grad_norm=tr["max_grad_norm"], seed=0)
    data = Batches(seed, tr["batch"], tr["seq_len"], cfg.vocab_size)
    trainer = Trainer(cfg, tc, seq_len=tr["seq_len"],
                      global_batch=tr["batch"], data=data)
    shapes = pt.abstract_params(lm.model_specs(cfg))
    params = weights.make(shapes, seed, device=jax.devices()[0])
    w0 = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(params)
    trainer.params, trainer.opt_state = params, trainer.tx.init(params)
    return cfg, trainer, data, w0


def step(trainer, data: Batches, n: int = 1) -> List[float]:
    """``n`` steps through ``Trainer.run``, on the next ``n`` batches."""
    res = trainer.run(n, params=trainer.params,
                      opt_state=trainer.opt_state, resume=False)
    data.offset += n
    return res.losses


def first_steps(trainer, data: Batches, w0, steps: int = 3) -> Dict:
    """The program's readings over its first ``steps`` steps."""
    losses = step(trainer, data)
    grad = [n / (1 - BETA1) for n in _norms(_adam_mu(trainer.opt_state))]
    for _ in range(steps - 1):
        losses += step(trainer, data)
    return {"losses": losses, "grad": grad,
            "update": _norms(trainer.params, w0)}


def run(cell, seed: int, seconds: float, trace: bool, *,
        backend: Optional[str] = None, limits: Dict[str, float],
        t_start: float) -> Dict:
    import jax
    from chipbench import serve_driver, xplane

    compiles = serve_driver.CompileLog()
    t = time.monotonic()
    cfg, trainer, data, w0 = build(cell, seed, backend)
    jax.block_until_ready((trainer.params, trainer.opt_state, w0))
    phases = {"build_s": time.monotonic() - t}
    t = time.monotonic()
    prog = first_steps(trainer, data, w0)
    phases["first_steps_s"] = time.monotonic() - t
    mix = cell.traffic
    setup_s = time.monotonic() - t_start
    log = {"setup": compiles.mark()}
    cpu0 = time.process_time()
    t0 = time.monotonic()
    n, traced, tdir = 0, None, None
    while time.monotonic() - t0 < seconds:
        if trace and traced is None and n == mix["trace_after"]:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(tdir,
                                     profiler_options=serve_driver._options())
            traced = [time.monotonic(), n]
        step(trainer, data)
        n += 1
        if traced is not None and len(traced) == 2 and \
                n - traced[1] >= mix["trace_steps"]:
            jax.profiler.stop_trace()
            traced += [time.monotonic(), n]
    t1 = time.monotonic()
    if traced is not None and len(traced) == 2:
        jax.profiler.stop_trace()
        traced += [time.monotonic(), n]
    log["window"] = compiles.since(log["setup"])
    host = {"cpu_s": time.process_time() - cpu0}
    mark = compiles.mark()
    devices = [jax.devices()[0]]
    mem = serve_driver.peak_bytes(devices)
    trace_out = None
    if traced is not None:
        trace_out = xplane.reduce_file(xplane.find_xplane(tdir),
                                       traced[2] - traced[0])
        trace_out["steps"] = traced[3] - traced[1]
        serve_driver._rmtree(tdir)
    trainer.params = trainer.opt_state = None
    del trainer
    gc.collect()
    t = time.monotonic()
    ref = reference_readings(cell, w0, data, len(prog["losses"]))
    after = {"reference_s": time.monotonic() - t}
    log["reference"] = compiles.since(mark)
    correct, checks = verdict(compare(prog, ref),
                              int(log["window"]["compiles"]), limits)
    tokens = n * mix["trainer"]["batch"] * mix["trainer"]["seq_len"]
    return {"correct": correct, "attempted": n, "failed": 0,
            "e2e": {"setup_s": setup_s, "train_tok_s": tokens / (t1 - t0)},
            "devices": devices, "memory_peak_bytes": mem, "checks": checks,
            "trace": trace_out, "setup_phases": phases,
            "after_window": after, "compile_log": log, "host": host}
