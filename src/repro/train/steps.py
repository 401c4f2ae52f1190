"""Step functions: train (with microbatched gradient accumulation), prefill
and decode. These are the units the launcher jits/lowers — both for real
execution and for the multi-pod dry-run."""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.models import common as cm
from repro.models import lm
from repro.optim import optimizer as opt
from repro.optim.compression import compress_gradients

PyTree = Any


def make_optimizer(tc: TrainConfig) -> opt.GradientTransformation:
    schedule = opt.warmup_cosine_schedule(tc.learning_rate, tc.warmup_steps,
                                          tc.total_steps)
    parts = []
    if tc.max_grad_norm:
        parts.append(opt.clip_by_global_norm(tc.max_grad_norm))
    if tc.grad_compression:
        parts.append(compress_gradients(tc.grad_compression,
                                        tc.grad_compression_ratio))
    parts.append(opt.scale_by_adam())
    if tc.weight_decay:
        parts.append(opt.add_decayed_weights(tc.weight_decay))
    parts.append(opt.scale_by_schedule(schedule))
    return opt.chain(*parts)


def make_train_step(cfg: ModelConfig, tx: opt.GradientTransformation,
                    microbatches: int = 1) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics); jitted, its program is ``jit_train_step``.

    ``microbatches > 1`` accumulates gradients over a lax.scan so peak
    activation memory scales with the microbatch, not the global batch —
    the standard large-model memory lever.
    """

    def loss_fn(params, mb):
        return lm.loss_fn(cfg, params, mb)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
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
            inv = 1.0 / microbatches
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            loss = lsum * inv
            metrics = {}

        gnorm = opt.clip_by_global_norm(1.0)  # reuse norm computation
        leaves = [g for g in jax.tree_util.tree_leaves(grads)
                  if g is not None]
        grad_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in leaves))
        updates, opt_state = tx.update(grads, opt_state, params)
        params = opt.apply_updates(params, updates)
        out_metrics = {"loss": loss, "grad_norm": grad_norm}
        out_metrics.update({k: v for k, v in metrics.items()})
        return params, opt_state, out_metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, chunks: int = 1) -> Callable:
    """Prefill, optionally processing the batch in ``chunks`` sequential
    sub-batches: full-sequence activation peaks scale 1/chunks while the
    caches assemble to the same final layout (big-model memory lever —
    prefill has no gradient so only the live set matters)."""
    if chunks <= 1:
        def step(params, batch, caches):
            return lm.prefill(cfg, params, batch, caches)
        return step

    def step(params, batch, caches):
        B = batch["tokens"].shape[0]
        assert B % chunks == 0, (B, chunks)
        Bc = B // chunks

        def split(x):
            return x.reshape((chunks, Bc) + x.shape[1:])

        mb = jax.tree_util.tree_map(split, batch)

        # Caches ride the scan CARRY with dynamic batch-slice updates —
        # reshaping/stacking them as scan ys would copy the whole KV stack
        # and break donation aliasing (measured: mistral prefill 13.5 GB ->
        # 74 GB/device with the copy formulation).
        # unit leaves: (R, B, ...) batch at axis 1; tail leaves: (B, ...).
        def body(carry, xs):
            mb_i, i = xs
            off = i * Bc
            sub = {
                "unit": jax.tree_util.tree_map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, off, Bc, 1),
                    carry["unit"]),
                "tail": jax.tree_util.tree_map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, off, Bc, 0),
                    carry["tail"]),
            }
            logits_i, new_sub = lm.prefill(cfg, params, mb_i, sub)
            carry = {
                "unit": jax.tree_util.tree_map(
                    lambda full, nc: jax.lax.dynamic_update_slice_in_dim(
                        full, nc.astype(full.dtype), off, 1),
                    carry["unit"], new_sub["unit"]),
                "tail": jax.tree_util.tree_map(
                    lambda full, nc: jax.lax.dynamic_update_slice_in_dim(
                        full, nc.astype(full.dtype), off, 0),
                    carry["tail"], new_sub["tail"]),
            }
            return carry, logits_i

        new_caches, logits = jax.lax.scan(body, caches,
                                          (mb, jnp.arange(chunks)))
        return logits.reshape((B,) + logits.shape[2:]), new_caches

    return step


def make_decode_step(cfg: ModelConfig) -> Callable:
    def step(params, token, caches, cur_pos):
        return lm.decode_step(cfg, params, token, caches, cur_pos)
    return step


def make_serve_step(cfg: ModelConfig,
                    sample_fn: Optional[Callable] = None) -> Callable:
    """The ``serve_step``: one token given a filled cache.

    Without ``sample_fn`` this is the dry-run's greedy step with the
    historical ``step(params, token, caches, cur_pos)`` signature. With a
    ``sample_fn(logits, rng) -> tokens`` (e.g. a bound
    :func:`repro.serve.sampling.sample_logits`) the returned step grows an
    ``rng`` argument and samples instead of argmaxing.
    """
    if sample_fn is None:
        def step(params, token, caches, cur_pos):
            logits, caches = lm.decode_step(cfg, params, token, caches,
                                            cur_pos)
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_token, logits, caches
        return step

    def sampled_step(params, token, caches, cur_pos, rng):
        logits, caches = lm.decode_step(cfg, params, token, caches, cur_pos)
        next_token = sample_fn(logits, rng)
        return next_token, logits, caches
    return sampled_step


def make_bucket_prefill_step(cfg: ModelConfig, max_len: int) -> Callable:
    """Prefill for the serving engine's bucketed admission path.

    Returns ``step(params, batch, last_pos) -> (logits, caches)``:
    ``batch["tokens"]`` is right-padded to a bucket length, ``last_pos (B,)``
    indexes each prompt's last real token, and the caches — built fresh
    inside the step at the engine's pool length ``max_len``, so jit never
    sees (or needs donation discipline for) a caller-held buffer — come out
    at full pool length ready for :func:`repro.models.lm.write_cache_slot`.
    One jit compilation per (bucket, batch) shape; the engine's
    ``CompileCache`` keys on exactly that.
    """
    def step(params, batch, last_pos):
        caches = lm.init_caches(cfg, batch["tokens"].shape[0], max_len)
        return lm.prefill_at(cfg, params, batch, caches, last_pos)
    return step


def make_pool_serve_step(cfg: ModelConfig,
                         sample_fn: Optional[Callable] = None,
                         paged: bool = False) -> Callable:
    """One decode tick over a serving engine's whole slot pool.

    ``step(params, tokens, caches, cur_pos, rng, active) -> (next, caches)``
    with everything per-slot: ``tokens (S,)`` each slot's previous token,
    ``cur_pos (S,)`` each slot's absolute write position (vector decode —
    see :func:`repro.models.lm.decode_step`), ``active (S,)`` bool masking
    slots that hold a live request. Inactive slots are computed but inert:
    their sampled token is replaced by their input token (so host-side slot
    state never moves) and whatever they write into their own cache row is
    dead — admission overwrites the full row. Slots are independent along
    the batch axis end to end, which is what makes engine outputs match the
    single-request oracle regardless of co-batched neighbors.

    ``paged=True`` grows a trailing ``page_table (S, P)`` argument and
    runs the paged cache layout. Pages are SHARED physical state — an
    inactive lane writing through its (stale) table row would clobber a
    page a later owner still needs — so inactive rows are redirected to
    the trash page before the model ever sees the table.
    """
    def _next(logits, tokens, rng, active):
        if sample_fn is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt = sample_fn(logits, rng)
        return jnp.where(active, nxt, tokens)

    if paged:
        def paged_step(params, tokens, caches, cur_pos, rng, active,
                       page_table):
            page_table = jnp.where(active[:, None], page_table, 0)
            logits, caches = lm.decode_step(cfg, params, tokens, caches,
                                            cur_pos, page_table=page_table)
            return _next(logits, tokens, rng, active), caches
        return paged_step

    def step(params, tokens, caches, cur_pos, rng, active):
        logits, caches = lm.decode_step(cfg, params, tokens, caches,
                                        cur_pos)
        return _next(logits, tokens, rng, active), caches
    return step


def make_chunk_prefill_step(cfg: ModelConfig) -> Callable:
    """Chunked prefill over the serving engine's slot pool (paged only).

    ``step(params, tokens, caches, start_pos, last_idx, active, page_table)
    -> (logits, h_last, caches)``: ``tokens (S, C)`` is one fixed-size
    prompt chunk per slot (zeros for slots with nothing to prefill this
    tick), ``start_pos (S,)`` the chunk's absolute start position,
    ``last_idx (S,)`` the within-chunk readout index (meaningful on a
    prompt's final chunk), ``h_last (S, E)`` the pre-final-norm backbone
    state at that index (the speculative draft anchor). ONE compile covers
    every prompt length — the engine admits a prompt as ``ceil(len / C)``
    invocations interleaved with decode ticks. Inactive lanes are
    redirected to the trash page exactly like the paged decode tick.
    """
    def step(params, tokens, caches, start_pos, last_idx, active,
             page_table):
        page_table = jnp.where(active[:, None], page_table, 0)
        return lm.prefill_chunk(cfg, params, tokens, caches, start_pos,
                                last_idx, page_table)
    return step


def make_draft_step(cfg: ModelConfig, k: int) -> Callable:
    """Draft proposer for draft-k-verify-1 speculative decoding.

    ``draft(params, anchor, last_token) -> drafts (S, k)``: from each
    slot's residual-stream anchor — the pre-final-norm backbone state at
    its last committed input position (returned by
    :func:`repro.models.lm.prefill_chunk` / :func:`~repro.models.lm.
    verify_chunk`) — propose ``k`` greedy continuations WITHOUT running
    the backbone. The draft state advances by embedding feedback alone
    (``g <- g + embed(token)``, the same scaled embedding the real
    residual stream starts from) and reads out through the model's OWN
    output head. On butterfly-compressed archs (``cfg.butterfly.sites``
    containing ``"lm_head"``) that head is the fixed-structure butterfly
    sandwich the paper builds — at 142x–273x fewer parameters than dense
    (``BENCH_quick.json`` ``params/*-head`` rows), i.e. the near-free
    draft model already living inside the architecture. Draft quality
    only affects speed, never output: greedy verification commits exactly
    the tokens the full model would have produced.
    """
    if k < 1:
        raise ValueError(f"draft step needs k >= 1, got {k}")

    def draft(params, anchor, last_token):
        g = anchor.astype(cfg.cdtype())
        tok = jnp.asarray(last_token, jnp.int32)
        out = []
        for _ in range(k):                 # k is small; unrolled
            g = g + cm.embed(cfg, params["embed"], tok[:, None])[:, 0]
            h = cm.rmsnorm(g[:, None], params["final_norm"], cfg.norm_eps)
            logits = cm.head_apply(cfg, params["head"], params["embed"], h)
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            out.append(tok)
        return jnp.stack(out, axis=1)
    return draft


def make_spec_decode_step(cfg: ModelConfig, k: int) -> Callable:
    """One speculative verify tick over a serving engine's slot pool.

    ``step(params, tokens, caches, cur_pos, active, page_table) ->
    (targets, accepted, anchor, caches)`` with ``tokens (S, k+1)`` each
    slot's last committed token followed by its ``k`` draft tokens at
    absolute positions ``cur_pos .. cur_pos+k``. ONE batched pass of the
    full model (:func:`repro.models.lm.verify_chunk`) produces greedy
    targets at every position; ``accepted (S,)`` is the per-slot length
    of the leading draft prefix that matches them (``0..k``), so the host
    commits ``accepted+1`` tokens ``targets[:, :accepted+1]`` and
    advances ``cur_pos`` by exactly that — rejected positions never
    advance ``cur_pos``, leaving their stale KV writes masked out.
    ``anchor (S, E)`` is the pre-final-norm backbone state at the last
    committed input position, seeding the next tick's draft state.

    Greedy-only by construction (targets are argmax): with greedy
    sampling the committed stream is token-identical to non-speculative
    decoding, which is what the CI parity gate asserts. Inactive lanes
    are trash-redirected and their outputs pinned to their inputs, like
    the pooled decode step.
    """
    if k < 1:
        raise ValueError(f"speculative decode needs k >= 1 drafts, got {k}")

    def step(params, tokens, caches, cur_pos, active, page_table):
        page_table = jnp.where(active[:, None], page_table, 0)
        logits, x, caches = lm.verify_chunk(cfg, params, tokens, caches,
                                            cur_pos, page_table)
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # draft j+1 survives iff it equals the model's target at position j
        # AND every earlier draft survived: leading-match prefix length
        matches = targets[:, :-1] == tokens[:, 1:]
        accepted = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1),
                           axis=1)
        accepted = jnp.where(active, accepted, 0)
        targets = jnp.where(active[:, None], targets, tokens)
        S = tokens.shape[0]
        anchor = x[jnp.arange(S), accepted]
        return targets, accepted, anchor, caches
    return step
