"""Encoder–decoder butterfly network (paper §4) and Theorem 1 apparatus.

Network: ``Ȳ = D · E · B · X`` with
  * ``X ∈ R^{n×d}`` data, ``Y ∈ R^{m×d}`` targets (Y = X for auto-encoders),
  * ``B``: ℓ×n truncated butterfly (log n stages + fixed truncation),
  * ``E ∈ R^{k×ℓ}`` dense encoder core, ``D ∈ R^{m×k}`` dense decoder,
  * loss ``L(Ȳ) = ||Ȳ − Y||_F²``.

Theorem 1: at any critical point of (D, E) with B fixed (satisfying the
rank/eigenvalue assumptions), ``L = tr(YYᵀ) − Σ_{i∈I} λ_i(Σ(B))`` for some
``I ⊆ [ℓ]``, and local minima have ``I = [k]`` — i.e. with B frozen, local
minima are global. This module provides the forward/loss, closed-form optima,
the Theorem 1 predicted loss, baselines (PCA, FJLT+PCA), and one/two-phase
gradient training used by the paper's §5.2/§5.3 experiments.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import butterfly as bf
from repro.kernels import context as exctx
from repro.kernels import ops as kops
from repro.optim import optimizer as opt


@dataclass(frozen=True)
class EncDecSpec:
    n: int          # input dim (rows of X)
    m: int          # output dim (rows of Y)
    d: int          # number of data columns
    k: int          # bottleneck
    ell: int        # butterfly truncation (k <= ell <= m <= n)
    jl_scale: bool = True
    trunc_idx: Tuple[int, ...] = ()

    @property
    def pad_n(self) -> int:
        return bf.padded_dim(self.n)


def make_spec(key: jax.Array, n: int, d: int, k: int,
              ell: Optional[int] = None, m: Optional[int] = None,
              eps: float = 0.5) -> EncDecSpec:
    """ℓ defaults to the Proposition 4.1 prescription ``k log k + k/eps``."""
    m = n if m is None else m
    if ell is None:
        ell = min(n, max(k + 1, int(math.ceil(k * math.log2(max(k, 2))
                                              + k / eps))))
    idx = bf.truncation_indices(key, bf.padded_dim(n), ell)
    return EncDecSpec(n=n, m=m, d=d, k=k, ell=ell, trunc_idx=idx)


def init_params(key: jax.Array, spec: EncDecSpec) -> Dict[str, jnp.ndarray]:
    kb, ke, kd = jax.random.split(key, 3)
    scale_e = 1.0 / math.sqrt(spec.ell)
    scale_d = 1.0 / math.sqrt(spec.k)
    return {
        "B": bf.fjlt_weights(kb, spec.pad_n),
        "E": scale_e * jax.random.normal(ke, (spec.k, spec.ell)),
        "D": scale_d * jax.random.normal(kd, (spec.m, spec.k)),
    }


def apply_B(spec: EncDecSpec, w: jnp.ndarray, X: jnp.ndarray, *,
            context: exctx.ContextLike = None) -> jnp.ndarray:
    """``B X`` for column-data ``X (n×d)`` -> (ℓ×d).

    The butterfly product dispatches through :mod:`repro.kernels.ops`; the
    fused Pallas path is differentiable (custom_vjp), so training through
    ``apply_B`` keeps the single-HBM-round-trip kernel in both directions.
    Execution policy — backend, tile sizes, mesh — rides ``context``
    (:mod:`repro.kernels.context`); a context with a mesh shards the data
    columns (the batch dim of the transposed product) over its data axes via
    :mod:`repro.runtime.butterfly_sharding`.
    """
    Xp = X
    if spec.pad_n != spec.n:
        Xp = jnp.pad(X, ((0, spec.pad_n - spec.n), (0, 0)))
    H = kops.butterfly_apply(Xp.T, w, context=context)  # (d, pad_n)
    Ht = bf.truncate(H, spec.trunc_idx, spec.pad_n, spec.jl_scale)
    return Ht.T                                        # (ℓ, d)


def forward(spec: EncDecSpec, params: Dict, X: jnp.ndarray, *,
            context: exctx.ContextLike = None) -> jnp.ndarray:
    Xt = apply_B(spec, params["B"], X, context=context)
    return params["D"] @ (params["E"] @ Xt)


def loss_fn(spec: EncDecSpec, params: Dict, X: jnp.ndarray,
            Y: jnp.ndarray, *,
            context: exctx.ContextLike = None) -> jnp.ndarray:
    Yb = forward(spec, params, X, context=context)
    return jnp.sum(jnp.square(Yb - Y))


# ---------------------------------------------------------------------------
# Theory: Σ(B), Theorem 1 prediction, closed-form optimum for fixed B
# ---------------------------------------------------------------------------

def _pinv(G: jnp.ndarray) -> jnp.ndarray:
    """Moore-Penrose with a 1e-6 relative cutoff."""
    return jnp.linalg.pinv(G, rtol=1e-6)


def sigma_B(spec: EncDecSpec, w: jnp.ndarray, X: jnp.ndarray,
            Y: jnp.ndarray) -> jnp.ndarray:
    """``Σ(B) = Y X̃ᵀ (X̃ X̃ᵀ)^{-1} X̃ Yᵀ`` with ``X̃ = B X`` (m×m, PSD)."""
    Xt = apply_B(spec, w, X)
    G = Xt @ Xt.T
    # pinv: when rank(X) < ℓ the Gram matrix is singular (Theorem 1's
    # assumption (a) fails); Moore-Penrose still yields the projection form
    # Σ(B) = Y Π_rowspace(X̃) Yᵀ, which is what the loss geometry uses.
    Ginv = _pinv(G)
    M = Y @ Xt.T
    return M @ Ginv @ M.T


def theorem1_loss(spec: EncDecSpec, w: jnp.ndarray, X: jnp.ndarray,
                  Y: jnp.ndarray, k: Optional[int] = None) -> jnp.ndarray:
    """Predicted loss at a local minimum with B fixed:
    ``tr(YYᵀ) − Σ_{i∈[k]} λ_i(Σ(B))``."""
    k = spec.k if k is None else k
    lam = jnp.linalg.eigvalsh(sigma_B(spec, w, X, Y))[::-1]
    return jnp.trace(Y @ Y.T) - jnp.sum(lam[:k])


def optimal_DE(spec: EncDecSpec, w: jnp.ndarray, X: jnp.ndarray,
               Y: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Closed-form global optimum of (D, E) for fixed B (Claim C.1 + I=[k]):
    ``D = U_k``, ``E = U_kᵀ Y X̃ᵀ (X̃X̃ᵀ)^{-1}``, U_k = top-k eigvecs of Σ(B)."""
    Xt = apply_B(spec, w, X)
    G = Xt @ Xt.T
    Ginv = _pinv(G)
    S = sigma_B(spec, w, X, Y)
    lam, U = jnp.linalg.eigh(S)
    Uk = U[:, ::-1][:, : spec.k]
    D = Uk
    E = Uk.T @ Y @ Xt.T @ Ginv
    return D, E


# ---------------------------------------------------------------------------
# Baselines (paper §5.2): PCA (= Δ_k) and FJLT+PCA (Proposition 4.1)
# ---------------------------------------------------------------------------

def pca_loss(X: jnp.ndarray, Y: jnp.ndarray, k: int) -> jnp.ndarray:
    """``Δ_k = ||Y_k − Y||_F²`` via exact SVD (auto-encoder: Y = X)."""
    s = jnp.linalg.svd(Y, compute_uv=False)
    return jnp.sum(jnp.square(s[k:]))


def sketch_rank_k(Xt: jnp.ndarray, X: jnp.ndarray, k: int) -> jnp.ndarray:
    """Best rank-k approximation of ``X`` from the rows of ``Xt`` (Sarlós):
    ``[X Π]_k`` with Π the projection onto rowspace(Xt)."""
    _, _, Vt = jnp.linalg.svd(Xt, full_matrices=False)   # (ℓ, d)
    XV = X @ Vt.T                                        # (n, ℓ)
    U2, S2, V2t = jnp.linalg.svd(XV, full_matrices=False)
    XVk = (U2[:, :k] * S2[:k]) @ V2t[:k]
    return XVk @ Vt


def fjlt_pca_loss(key: jax.Array, X: jnp.ndarray, k: int, ell: int
                  ) -> jnp.ndarray:
    """``||J_k(X) − X||_F²`` with J an ℓ×n FJLT (Proposition 4.1 baseline)."""
    n = X.shape[0]
    pad_n = bf.padded_dim(n)
    kw, ki = jax.random.split(key)
    w = bf.fjlt_weights(kw, pad_n)
    idx = bf.truncation_indices(ki, pad_n, ell)
    spec = EncDecSpec(n=n, m=n, d=X.shape[1], k=k, ell=ell, trunc_idx=idx)
    Xt = apply_B(spec, w, X)
    Xk = sketch_rank_k(Xt, X, k)
    return jnp.sum(jnp.square(X - Xk))


# ---------------------------------------------------------------------------
# Training (paper §5.2 one-phase, §5.3 two-phase)
# ---------------------------------------------------------------------------

def train(spec: EncDecSpec, params: Dict, X: jnp.ndarray, Y: jnp.ndarray,
          steps: int, lr: float = 1e-3, train_B: bool = True,
          log_every: int = 0,
          context: exctx.ContextLike = None) -> Tuple[Dict, list]:
    """Full-batch Adam on the reconstruction loss.

    ``train_B=False`` freezes the butterfly (phase 1 of two-phase learning).
    ``context`` carries the kernel execution policy — on TPU the fused
    Pallas kernel runs in the gradient too (custom_vjp); unset tile knobs
    are autotuned; a context with a mesh data-shards the butterfly product
    across devices. Returns (params, loss history).
    """
    tx = opt.adamw(lr)
    state = tx.init(params)

    def masked_loss(p):
        return loss_fn(spec, p, X, Y, context=context)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(masked_loss)(params)
        if not train_B:
            grads = dict(grads, B=jnp.zeros_like(grads["B"]))
        updates, state = tx.update(grads, state, params)
        params = opt.apply_updates(params, updates)
        return params, state, loss

    history = []
    for i in range(steps):
        params, state, loss = step(params, state)
        if log_every and (i % log_every == 0 or i == steps - 1):
            history.append(float(loss))
    return params, history


def train_two_phase(spec: EncDecSpec, params: Dict, X: jnp.ndarray,
                    Y: jnp.ndarray, steps1: int, steps2: int,
                    lr: float = 1e-3, log_every: int = 0,
                    context: exctx.ContextLike = None
                    ) -> Tuple[Dict, list, list]:
    """§5.3: phase 1 trains (D, E) with B frozen at its FJLT init (Theorem 1
    guarantees local = global here); phase 2 fine-tunes all three."""
    params, h1 = train(spec, params, X, Y, steps1, lr=lr, train_B=False,
                       log_every=log_every, context=context)
    params, h2 = train(spec, params, X, Y, steps2, lr=lr, train_B=True,
                       log_every=log_every, context=context)
    return params, h1, h2
