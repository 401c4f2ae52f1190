"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jax; ``get_topology_desc`` describes a
``v5e:2x2`` slice it can compile for while JAX itself runs on the CPU.
Each test lowers one fused kernel at ``smollm-135m-butterfly`` widths with
the tiles the autotuner picks (no ``block_b``/``segment`` passed) and
compiles it: Mosaic refuses what interpret mode accepts — lane-splitting
reshapes, unaligned sublane offsets, non-32-bit rotates, VMEM overruns.

The topology is described inside a module-scoped fixture (never at import,
in a ``skipif`` or a ``parametrize``): only one process may load the TPU
library, and only the worker that runs this file should.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import layers
from repro.kernels import context as exctx
from repro.kernels.butterfly import butterfly_matmul
from repro.kernels.flash import flash_attention
from repro.kernels.paged_attention import (_paged_decode_pallas,
                                           paged_decode_attention)
from repro.kernels.sandwich import sandwich_matmul

D_MODEL, D_FF, VOCAB = 576, 1536, 49152          # smollm-135m
KV_HEADS, GROUP, HEAD_DIM, PAGE = 3, 3, 64, 16
ROWS = 1024                                        # activation rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


def _grad(fn, argnums):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=argnums)


def _stages(n: int) -> int:
    return n.bit_length() - 1


@pytest.mark.parametrize("mode", ["fwd", "bwd"])
@pytest.mark.parametrize("n", [1024, 65536])
def test_butterfly_compiles(one_chip, n, mode):
    x = jax.ShapeDtypeStruct((ROWS, n), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((_stages(n), 2, n), jnp.bfloat16,
                             sharding=one_chip)
    fn = butterfly_matmul if mode == "fwd" else _grad(butterfly_matmul,
                                                      (0, 1))
    _compile(fn, x, w)


@pytest.mark.parametrize("mode", ["fwd", "bwd"])
@pytest.mark.parametrize("site", ["lm_head", "mlp_up", "mlp_down"])
def test_sandwich_compiles(one_chip, site, mode):
    n_in, n_out = {"lm_head": (D_MODEL, VOCAB), "mlp_up": (D_MODEL, D_FF),
                   "mlp_down": (D_FF, D_MODEL)}[site]
    k_in, k_out = layers.default_k(n_in), layers.default_k(n_out)
    p_in, p_out = (1 << (n_in - 1).bit_length()), (1 << (n_out - 1)
                                                   .bit_length())

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (s((ROWS, p_in)), s((_stages(p_in), 2, p_in)),
            s((p_in, k_in), jnp.float32), s((k_out, k_in), jnp.float32),
            s((k_out, p_out), jnp.float32), s((_stages(p_out), 2, p_out)))
    fn = (sandwich_matmul if mode == "fwd"
          else _grad(sandwich_matmul, (0, 1, 3, 5)))
    _compile(fn, *args)


def _paged_shapes(sharding, slots=8):
    pages_per_slot = 2048 // PAGE
    pages = slots * pages_per_slot + 1                # + the trash page

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (s((slots, KV_HEADS, GROUP, HEAD_DIM)),
            s((pages, PAGE, KV_HEADS, HEAD_DIM)),
            s((pages, PAGE, KV_HEADS, HEAD_DIM)),
            s((slots, pages_per_slot), jnp.int32), s((slots,), jnp.int32))


def test_paged_decode_compiles(one_chip):
    _compile(lambda q, k, v, pt, cp: _paged_decode_pallas(
        q, k, v, pt, cp, interpret=False), *_paged_shapes(one_chip))


def test_paged_decode_compiles_on_four_chip_mesh(topo):
    """The mesh-sharded engine's decode: XLA refuses to partition a Mosaic
    kernel, so under a ("data",) mesh it must run inside shard_map."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    ctx = exctx.resolve_execution(exctx.ExecutionContext(backend="pallas",
                                                         mesh=mesh))

    def decode(*args):
        with exctx.use_execution(ctx):
            return paged_decode_attention(*args)

    _compile(decode, *_paged_shapes(NamedSharding(mesh, PartitionSpec()),
                                    slots=6))


@pytest.mark.parametrize("mode", ["fwd", "bwd"])
def test_flash_compiles(one_chip, mode):
    q = jax.ShapeDtypeStruct((1, KV_HEADS * GROUP, 1024, HEAD_DIM),
                             jnp.bfloat16, sharding=one_chip)
    fn = (flash_attention if mode == "fwd"
          else _grad(flash_attention, (0, 1, 2)))
    _compile(fn, q, q, q)
