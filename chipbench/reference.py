"""Plain reference of the served and trained model, in float32 at full
matmul precision, written from the published architecture and importing
nothing of the program.

SmolLM is a Llama decoder: token embedding, per layer RMSNorm →
grouped-query attention with rotary positions (rotate-half) → residual,
RMSNorm → SwiGLU MLP → residual, then a final RMSNorm and the output head
(tied to the embedding in the dense model). Where the configuration file
names the paper's butterfly sites, a projection ``W`` is the §3.2 sandwich
``J2ᵀ · W' · J1``: a butterfly over the input padded to a power of two,
truncated to ``k_in`` fixed coordinates scaled by ``sqrt(n/k)``, a dense
``k_out × k_in`` core, the transpose of that for the output butterfly.

Departures, each stated in the configuration file's ``departures``: the
embedding is multiplied by ``sqrt(hidden_size)`` (the program does so for
every architecture; SmolLM does not).

The weights are the benchmark's own (``chipbench.weights``); the reference
reads them by the names of the program's parameter tree. The fixed
truncation coordinates of each butterfly site are part of the model's
definition; they are derived here by the recipe the model states (a CRC32
of the site name mixed with the butterfly seed, then a draw without
replacement).

``quant`` rounds every matmul operand before use: ``None`` for the
reference, ``"fp8"`` (float8 e4m3 with one scale per tensor) for the
control that must come out as not correct.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# Precision of the matmul operands
# ---------------------------------------------------------------------------

def _round(a: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
    if quant is None:
        return a
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


# ---------------------------------------------------------------------------
# Butterfly sandwich
# ---------------------------------------------------------------------------

def padded(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def default_k(n: int, k_factor: float) -> int:
    return min(max(1, int(round(k_factor * math.log2(max(n, 2))))), n)


@functools.lru_cache(maxsize=None)
def site_indices(bseed: int, site: str, n_in: int, n_out: int,
                 k_factor: float) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The fixed truncation coordinates of one butterfly site."""
    h = zlib.crc32(site.encode()) ^ (bseed * 2654435761 & 0x7FFFFFFF)
    out = []
    with jax.ensure_compile_time_eval():      # called while tracing
        key = jax.random.PRNGKey(h & 0x7FFFFFFF)
        for k, n in zip(jax.random.split(key), (n_in, n_out)):
            ell = default_k(n, k_factor)
            idx = jax.random.choice(k, padded(n), shape=(ell,),
                                    replace=False)
            out.append(tuple(int(i) for i in np.sort(np.asarray(idx))))
    return out[0], out[1]


def _partner(x: jnp.ndarray, stride: int) -> jnp.ndarray:
    """``y[i] = x[i xor stride]`` along the last axis: flipping bit
    ``log2(stride)`` of the index swaps the two halves of each block of
    ``2 stride``. A reshape and a flip, not a gather over a table of
    indices, which made the compiled training reference 3.7 times larger
    and its compile twice as long (v5e compile, 279 MB of code against
    76 MB)."""
    n = x.shape[-1]
    y = x.reshape(x.shape[:-1] + (n // (2 * stride), 2, stride))
    return jnp.flip(y, axis=-2).reshape(x.shape)


def butterfly(w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``B x``: stage s maps ``x[i] -> a_s[i] x[i] + b_s[i] x[i^2^s]``,
    stage 0 first."""
    for s in range(w.shape[0]):
        x = w[s, 0] * x + w[s, 1] * _partner(x, 1 << s)
    return x


def butterfly_t(w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``Bᵀ x``: the stages transposed, last stage first."""
    for s in reversed(range(w.shape[0])):
        x = w[s, 0] * x + _partner(w[s, 1] * x, 1 << s)
    return x


def sandwich(p: Dict, x: jnp.ndarray, site: str, n_out: int, bcfg: Dict,
             quant: Optional[str]) -> jnp.ndarray:
    n_in = x.shape[-1]
    idx_in, idx_out = site_indices(bcfg["seed"], site, n_in, n_out,
                                   bcfg["k_factor"])
    N1, N2 = padded(n_in), padded(n_out)
    x = jnp.pad(_round(x, quant), [(0, 0)] * (x.ndim - 1) + [(0, N1 - n_in)])
    h = butterfly(_round(p["b_in"], quant), x)
    h = jnp.take(h, jnp.asarray(idx_in), axis=-1) * math.sqrt(
        N1 / len(idx_in))
    h = _round(h, quant) @ _round(p["core"], quant).T
    z = jnp.zeros(h.shape[:-1] + (N2,), h.dtype)
    z = z.at[..., jnp.asarray(idx_out)].set(
        h * math.sqrt(N2 / len(idx_out)))
    z = butterfly_t(_round(p["b_out"], quant), z)
    return z[..., :n_out]


# ---------------------------------------------------------------------------
# The decoder
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotate-half rotary embedding; x (S, heads, D), pos (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(p, x, site, n_out, mc, quant):
    if "w" in p:
        return _round(x, quant) @ _round(p["w"], quant)
    return sandwich(p, x, site, n_out, mc["butterfly"], quant)


def layer(mc: Dict, lp: Dict, x: jnp.ndarray, quant) -> jnp.ndarray:
    """One decoder layer over a whole sequence x (S, E), causal."""
    S = x.shape[0]
    H, KV = mc["num_attention_heads"], mc["num_key_value_heads"]
    D = mc["head_dim"]
    eps, theta = mc["rms_norm_eps"], mc["rope_theta"]
    pos = jnp.arange(S)
    a = lp["attn"]
    h = _round(rmsnorm(x, lp["norm1"], eps), quant)
    q = rope(jnp.einsum("se,ehd->shd", h, _round(a["wq"], quant)), pos,
             theta)
    k = rope(jnp.einsum("se,ekd->skd", h, _round(a["wk"], quant)), pos,
             theta)
    v = jnp.einsum("se,ekd->skd", h, _round(a["wv"], quant))
    q = q.reshape(S, KV, H // KV, D)
    s = jnp.einsum("qkgd,skd->kgqs", _round(q, quant),
                   _round(k, quant)) * D ** -0.5
    s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None], s,
                  NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", _round(prob, quant), _round(v, quant))
    o = o.reshape(S, H, D)
    x = x + jnp.einsum("shd,hde->se", _round(o, quant),
                       _round(a["wo"], quant))
    h = rmsnorm(x, lp["norm2"], eps)
    f = lp["ffn"]
    F = mc["intermediate_size"]
    up = _linear(f["up"], h, "mlp_up", F, mc, quant)
    gate = _linear(f["gate"], h, "mlp_gate", F, mc, quant)
    return x + _linear(f["down"], jax.nn.silu(gate) * up, "mlp_down",
                       mc["hidden_size"], mc, quant)


def hidden(mc: Dict, w: Dict, tokens: jnp.ndarray, quant=None,
           remat: bool = False) -> jnp.ndarray:
    """Final-normed hidden states (S, E) of one sequence."""
    E = mc["hidden_size"]
    x = jnp.take(w["embed"]["table"], tokens, axis=0) * math.sqrt(E)

    def body(x, lp):
        return layer(mc, lp, x, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["unit"][0])
    return rmsnorm(x, w["final_norm"], mc["rms_norm_eps"])


def head(mc: Dict, w: Dict, h: jnp.ndarray, quant=None) -> jnp.ndarray:
    V = mc["vocab_size"]
    if mc["tie_word_embeddings"]:
        return _round(h, quant) @ _round(w["embed"]["table"], quant).T
    return _linear(w["head"], h, "lm_head", V, mc, quant)


def logits(mc: Dict, w: Dict, tokens: jnp.ndarray, quant=None
           ) -> jnp.ndarray:
    """(S, V) float32 logits of one sequence."""
    return head(mc, w, hidden(mc, w, tokens, quant), quant)


# ---------------------------------------------------------------------------
# Served tokens against the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 3))
def _gaps(mc_items, w, tokens, quant, n_prompt, n_total):
    """Per position: the reference's best logit minus the logit of the
    token that came next (the served token), and the same for the token
    the quantized model puts first; positions outside the served span
    read 0."""
    mc = dict(mc_items)
    mc["butterfly"] = dict(mc["butterfly"]) if mc.get("butterfly") else None
    with jax.default_matmul_precision("highest"):
        ref = logits(mc, w, tokens)
        best = jnp.max(ref, axis=-1)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        served_gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
        if quant is None:
            ctl_gap = jnp.zeros_like(served_gap)
        else:
            low = logits(mc, w, tokens, quant)
            pick = jnp.argmax(low, axis=-1)
            ctl_gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    pos = jnp.arange(tokens.shape[0])
    # position p predicts token p+1; the served tokens sit at
    # n_prompt .. n_total-1, so they are predicted at n_prompt-1 .. n_total-2
    span = (pos >= n_prompt - 1) & (pos < n_total - 1)
    return (jnp.where(span, served_gap, 0.0),
            jnp.where(span, ctl_gap, 0.0))


def _hashable(mc: Dict):
    items = []
    for k, v in sorted(mc.items()):
        if isinstance(v, dict):
            v = tuple(sorted((a, tuple(b) if isinstance(b, list) else b)
                             for a, b in v.items()))
        elif isinstance(v, list):
            v = tuple(v)
        items.append((k, v))
    return tuple(items)


def served_gaps(mc: Dict, w: Dict, prompt: Sequence[int],
                served: Sequence[int], length: int,
                quant: Optional[str] = None) -> Dict[str, float]:
    """How far the served tokens of one request lie below the reference's
    best logit: the widest gap and the sum of the gaps over its served
    tokens, and (with ``quant``) the same for the tokens the quantized
    control puts first at those positions. ``length`` pads every sequence
    to one compiled size."""
    seq = np.zeros(length, np.int32)
    n = len(prompt) + len(served)
    if n > length:
        raise ValueError(f"sequence of {n} tokens exceeds {length}")
    seq[:len(prompt)] = prompt
    seq[len(prompt):n] = served
    g, c = _gaps(_hashable(mc), w, jnp.asarray(seq), quant,
                 jnp.int32(len(prompt)), jnp.int32(n))
    return {"max": float(jnp.max(g)), "sum": float(jnp.sum(g)),
            "control_max": float(jnp.max(c)),
            "control_sum": float(jnp.sum(c)), "tokens": len(served)}


# ---------------------------------------------------------------------------
# Training: loss, gradients and three AdamW steps
# ---------------------------------------------------------------------------

def _seq_nll(mc: Dict, w: Dict, tokens: jnp.ndarray, quant, chunk: int
             ) -> jnp.ndarray:
    """Summed next-token negative log-likelihood of one sequence; the head
    runs in rematerialized blocks of ``chunk`` positions so its wide
    activations never all live at once."""
    S = tokens.shape[0]
    h = hidden(mc, w, tokens, quant, remat=True)
    tgt = jnp.concatenate([tokens[1:], tokens[:1]])
    valid = jnp.arange(S) < S - 1

    @jax.checkpoint
    def block(args):
        hb, tb, vb = args
        lg = head(mc, w, hb, quant)
        nll = (jax.nn.logsumexp(lg, axis=-1)
               - jnp.take_along_axis(lg, tb[:, None], -1)[:, 0])
        return jnp.sum(jnp.where(vb, nll, 0.0))

    chunk = math.gcd(S, chunk)
    n = S // chunk
    parts = jax.lax.map(block, (h.reshape(n, chunk, -1),
                                tgt.reshape(n, chunk),
                                valid.reshape(n, chunk)))
    return jnp.sum(parts)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _loss_grads(mc_items, w, batch, quant, chunk):
    mc = dict(mc_items)
    mc["butterfly"] = dict(mc["butterfly"]) if mc.get("butterfly") else None
    B, S = batch.shape
    zero = jax.tree_util.tree_map(jnp.zeros_like, w)

    def row(carry, toks):
        l, g = jax.value_and_grad(
            lambda p: _seq_nll(mc, p, toks, quant, chunk))(w)
        return (carry[0] + l,
                jax.tree_util.tree_map(jnp.add, carry[1], g)), None

    with jax.default_matmul_precision("highest"):
        (lsum, gsum), _ = jax.lax.scan(row, (jnp.zeros(()), zero), batch)
    denom = B * (S - 1)
    return lsum / denom, jax.tree_util.tree_map(lambda g: g / denom, gsum)


def loss_grads(mc: Dict, w: Dict, batch: np.ndarray, quant=None,
               chunk: int = 256):
    """Mean next-token loss of a (B, S) batch and its float32 gradients."""
    return _loss_grads(_hashable(mc), w, jnp.asarray(batch), quant, chunk)


def lr_at(hp: Dict, step: int) -> float:
    """Warmup then cosine to ``final_frac`` of the peak, at 0-based step."""
    peak, warm, total = hp["learning_rate"], hp["warmup_steps"], \
        hp["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    f = 0.1
    return peak * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * prog)))


@jax.jit
def _adamw(w, g, mu, nu, count, lr, hp_arr):
    clip, b1, b2, eps, wd = (hp_arr[i] for i in range(5))
    leaves = jax.tree_util.tree_leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, clip / (gn + 1e-12)), g)
    mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                nu, g)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def upd(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if p.ndim > 1:
            u = u + wd * p
        return p - lr * u

    return jax.tree_util.tree_map(upd, w, mu, nu), g, mu, nu


@jax.jit
def _zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def train_steps(mc: Dict, w0: Dict, batches, hp: Dict, quant=None,
                chunk: int = 256):
    """Follow the program's first steps: each step's loss, the first
    step's clipped gradient (what the optimizer receives) and the weights
    after the last step. AdamW as the trainer states it: clip by global
    norm, Adam moments with bias correction, decoupled decay of matrices
    only, learning rate from :func:`lr_at`."""
    w = w0
    mu = nu = _zeros_like(w0)
    hp_arr = jnp.asarray([hp["max_grad_norm"], 0.9, 0.999, 1e-8,
                          hp["weight_decay"]], jnp.float32)
    losses, first = [], None
    for k, b in enumerate(batches):
        loss, g = loss_grads(mc, w, b, quant, chunk)
        w, g, mu, nu = _adamw(w, g, mu, nu, jnp.float32(k + 1),
                              jnp.float32(lr_at(hp, k)), hp_arr)
        losses.append(float(loss))
        if first is None:
            first = g
    return losses, first, w
