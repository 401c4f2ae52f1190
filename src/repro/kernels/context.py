"""One object for every kernel-execution knob: :class:`ExecutionContext`.

Before this module, running a butterfly kernel anywhere above
:mod:`repro.kernels` meant threading four loose kwargs (``backend``,
``block_b``, ``segment``, ``mesh``/``mesh_axes``) through every call site
from ``kernels/ops.py`` up to the ``Trainer``, plus three env-var families.
All of that policy now lives in one frozen, hashable dataclass with a single
resolution order:

    explicit ``context=`` arg
      > ambient ``with use_execution(ctx):``
        > layer/config default (``ButterflyConfig`` via
          :meth:`ExecutionContext.from_butterfly_config`)
          > ``REPRO_*`` environment variables
            > autotuner / platform default

Per *field*: an unset field (``backend="auto"``, everything else ``None``)
falls through to the next layer, so a context only ever has to say what it
wants to change. :func:`resolve_execution` folds the layers and finalizes the
result — concrete backend (env override read once per process, see
:func:`resolve_backend`/:func:`clear_backend_cache`) and a built
:class:`~jax.sharding.Mesh` — into a context that is safe to close over in
jit and to use as an lru/jit cache key.

The pre-context loose kwargs (``backend=``, ``block_b=``, ``segment=``,
``mesh=``, ``mesh_axes=``) had a one-release deprecation shim; it is gone
— the entry points now reject unknown kwargs with a plain ``TypeError``,
and the CI examples step still runs under ``-W error::DeprecationWarning``
as a tripwire for any future shim.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Literal, Optional, Tuple, Union

import jax
from jax.sharding import Mesh

__all__ = [
    "Backend",
    "CONCRETE_BACKENDS",
    "ExecutionContext",
    "use_execution",
    "current_execution",
    "resolve_execution",
    "resolve_backend",
    "clear_backend_cache",
]

Backend = Literal["auto", "jnp", "pallas", "pallas_interpret"]

CONCRETE_BACKENDS = ("jnp", "pallas", "pallas_interpret")

ContextLike = Union["ExecutionContext", str, None]


# ---------------------------------------------------------------------------
# Backend resolution (cached REPRO_KERNEL_BACKEND read)
# ---------------------------------------------------------------------------

_ENV_UNREAD = "\x00unread"
_env_backend_cache: str = _ENV_UNREAD


def _env_backend() -> str:
    """``REPRO_KERNEL_BACKEND``, read from the environment once per process.

    The kernels resolve their backend at trace time on every call; hitting
    ``os.environ`` each time is both a per-call cost and a door for the env
    var to flip mid-process and silently split a model across two backends.
    """
    global _env_backend_cache
    if _env_backend_cache == _ENV_UNREAD:
        _env_backend_cache = os.environ.get(
            "REPRO_KERNEL_BACKEND", "").strip().lower()
    return _env_backend_cache


def clear_backend_cache() -> None:
    """Forget the cached ``REPRO_KERNEL_BACKEND`` read (tests only).

    Production code sets the env var before the process starts; a test that
    monkeypatches it must call this before and after, or the first resolver
    call in the process pins the old value.
    """
    global _env_backend_cache
    _env_backend_cache = _ENV_UNREAD


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: Backend = "auto") -> str:
    """Resolve ``auto`` to a concrete backend.

    A concrete ``backend`` (from an :class:`ExecutionContext` or a
    ``ButterflyConfig``) is validated and returned as-is — the context chain
    is the only override path. ``auto`` falls through to the cached
    ``REPRO_KERNEL_BACKEND`` env read, then the platform default (fused
    Pallas on TPU, the jnp oracle elsewhere).
    """
    if backend == "auto":
        env = _env_backend()
        if env and env != "auto":
            backend = env
        else:
            backend = "pallas" if _on_tpu() else "jnp"
    if backend not in CONCRETE_BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; expected one "
                         f"of {('auto',) + CONCRETE_BACKENDS}")
    return backend


# ---------------------------------------------------------------------------
# The context object
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ExecutionContext:
    """Execution policy for the fused butterfly/sandwich/flash kernels.

    Every field has an "unset" default that falls through to the next layer
    of the resolution order (module docstring); a context therefore composes:
    ``ctx.over(base)`` keeps ``ctx``'s set fields and fills the rest from
    ``base``.

    * ``backend`` — kernel path: ``"auto" | "jnp" | "pallas" |
      "pallas_interpret"`` (``"auto"`` = unset: env var, then platform).
    * ``block_b`` / ``segment`` — Pallas batch-tile rows and backward
      checkpoint interval; ``None`` = ``REPRO_TUNE_*`` env, then the
      :mod:`repro.kernels.tuning` autotuner.
    * ``mesh_shape`` — opt-in multi-device execution: ``(8,)`` builds a
      ``("data",)`` mesh, ``(2, 4)`` a ``("pod", "data")`` mesh
      (:func:`repro.launch.mesh.butterfly_mesh`); activations batch-shard
      under ``shard_map`` with replicated weights and psum'd weight grads.
    * ``mesh`` — an explicit prebuilt Mesh; wins over ``mesh_shape``.
    * ``mesh_axes`` — which mesh axes to batch-shard over (default: the
      ``("pod", "data")`` candidates filtered to the mesh).
    * ``vmem_budget`` / ``flash_block_q`` — autotuner overrides: VMEM bytes
      the footprint model may spend, and a forced flash q/kv block size
      (``None`` = ``REPRO_TUNE_VMEM_BUDGET`` / ``REPRO_TUNE_BLOCK_Q`` env,
      then the model defaults). Read ambiently by
      :mod:`repro.kernels.tuning`.

    Hashable and frozen: safe to close over in jit, to key lru caches on,
    and to store on a module (:class:`repro.nn.ButterflyLinear`).
    """

    backend: str = "auto"
    block_b: Optional[int] = None
    segment: Optional[int] = None
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axes: Optional[Tuple[str, ...]] = None
    mesh: Optional[Mesh] = None
    vmem_budget: Optional[int] = None
    flash_block_q: Optional[int] = None

    def __post_init__(self):
        if self.backend not in ("auto",) + CONCRETE_BACKENDS:
            raise ValueError(
                f"unknown kernel backend {self.backend!r}; expected one of "
                f"{('auto',) + CONCRETE_BACKENDS}")
        if self.mesh_shape is not None:
            object.__setattr__(self, "mesh_shape",
                               tuple(int(s) for s in self.mesh_shape))
        if self.mesh_axes is not None:
            object.__setattr__(self, "mesh_axes",
                               tuple(str(a) for a in self.mesh_axes))

    # -- composition ------------------------------------------------------

    @classmethod
    def coerce(cls, value: ContextLike) -> Optional["ExecutionContext"]:
        """``None`` | backend string | context -> context (or ``None``).

        Accepting a bare backend string keeps the common case terse:
        ``butterfly_apply(x, w, context="pallas_interpret")``.
        """
        if value is None or isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls(backend=value)
        raise TypeError(
            f"context must be an ExecutionContext, a backend string, or "
            f"None; got {type(value).__name__}")

    @classmethod
    def from_butterfly_config(cls, bc) -> "ExecutionContext":
        """The config layer of the resolution order: lift the execution
        fields of a :class:`repro.configs.base.ButterflyConfig` (or ``None``)
        into a context."""
        if bc is None:
            return cls()
        return cls(backend=bc.backend, block_b=bc.block_b,
                   segment=bc.segment, mesh_shape=bc.mesh_shape)

    def over(self, base: Optional["ExecutionContext"]
             ) -> "ExecutionContext":
        """This context's set fields over ``base``'s (field-wise overlay)."""
        if base is None:
            return self
        kw = {}
        for f in dataclasses.fields(self):
            mine = getattr(self, f.name)
            kw[f.name] = mine if mine != f.default else getattr(base, f.name)
        return ExecutionContext(**kw)

    def local(self) -> "ExecutionContext":
        """The same policy without the mesh: what one shard of a sharded
        region runs (prevents the shard_map wrappers from re-routing)."""
        if self.mesh is None and self.mesh_shape is None:
            return self
        return dataclasses.replace(self, mesh=None, mesh_shape=None)

    # -- introspection ----------------------------------------------------

    def mesh_layout(self) -> str:
        """``"data=8"``-style summary of the resolved mesh ("" if none)."""
        if self.mesh is None:
            return ""
        return ",".join(f"{a}={s}" for a, s in self.mesh.shape.items())

    def describe(self) -> str:
        """One-line summary of every set field (logs, ``TrainResult``)."""
        parts = [f"backend={self.backend}"]
        for name in ("block_b", "segment", "vmem_budget", "flash_block_q"):
            v = getattr(self, name)
            if v is not None:
                parts.append(f"{name}={v}")
        layout = self.mesh_layout()
        if layout:
            parts.append(f"mesh={layout}")
        elif self.mesh_shape is not None:
            parts.append(f"mesh_shape={self.mesh_shape}")
        if self.mesh_axes is not None:
            parts.append(f"mesh_axes={self.mesh_axes}")
        return " ".join(parts)


# ---------------------------------------------------------------------------
# Ambient context (mirrors runtime.sharding.use_sharding)
# ---------------------------------------------------------------------------

_STACK: list = []


class use_execution:
    """``with use_execution(ctx):`` — install an ambient execution context.

    Everything traced inside the block (every kernel entry point, layer,
    model, and the autotuner) sees ``ctx`` at the ambient layer of the
    resolution order. Blocks nest: the inner context's set fields win, unset
    fields fall through to the outer block.

    The ambient context is *trace-time* state, like ``use_sharding``: it is
    baked in when a function traces and is not part of jax's jit cache key.
    A function jitted and first called under one ambient context will NOT
    retrace when later called under another — wrap the ``use_execution``
    block *inside* the jitted function (so the context is a trace-time
    constant of that function), or pass an explicit ``context=`` argument,
    when a call site needs to switch policies across calls. The ``Trainer``
    freezes one resolved context per run for exactly this reason.
    """

    def __init__(self, context: ContextLike):
        ctx = ExecutionContext.coerce(context)
        self.ctx = ctx if ctx is not None else ExecutionContext()

    def __enter__(self) -> ExecutionContext:
        _STACK.append(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        _STACK.pop()
        return False


def current_execution() -> Optional[ExecutionContext]:
    """The folded ambient context (innermost set fields win), or ``None``."""
    if not _STACK:
        return None
    merged = _STACK[0]
    for ctx in _STACK[1:]:
        merged = ctx.over(merged)
    return merged


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

def _resolve_mesh(merged: ExecutionContext) -> Optional[Mesh]:
    if merged.mesh is not None:
        return merged.mesh
    if merged.mesh_shape is None:
        return None
    # a live sharding context's mesh (the Trainer installs one built from
    # this same shape) is reused instead of building a fresh one — but only
    # when its layout actually IS the requested shape: a context that
    # explicitly asks for a different mesh_shape must win over the ambient
    # mesh (the documented resolution order). butterfly_mesh is lru-cached,
    # so both roads usually lead to the same Mesh object anyway.
    from repro.runtime import sharding as rsharding
    sctx = rsharding.active_ctx()
    if (sctx is not None and sctx.mesh is not None
            and tuple(sctx.mesh.shape.values()) == merged.mesh_shape):
        return sctx.mesh
    from repro.launch.mesh import butterfly_mesh
    return butterfly_mesh(merged.mesh_shape)


def resolve_execution(context: ContextLike = None,
                      default: ContextLike = None) -> ExecutionContext:
    """Fold the resolution order into one finalized context.

    ``context`` is the explicit per-call layer, ``default`` the layer/config
    layer (e.g. :meth:`ExecutionContext.from_butterfly_config`); the ambient
    :func:`use_execution` stack sits between them. The result has a concrete
    ``backend`` and a built ``mesh`` (or ``None``); ``block_b``/``segment``
    may remain ``None``, meaning the ``REPRO_TUNE_*`` env vars and then the
    autotuner decide at kernel-call time. Idempotent: resolving an already
    finalized context returns it unchanged.
    """
    merged = ExecutionContext.coerce(context) or ExecutionContext()
    merged = merged.over(current_execution())
    merged = merged.over(ExecutionContext.coerce(default))
    return dataclasses.replace(merged,
                               backend=resolve_backend(merged.backend),
                               mesh=_resolve_mesh(merged))
