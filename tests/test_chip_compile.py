"""The main path's Pallas kernels compile for a TPU v5e chip.

A chip is described, not attached: the TPU compiler installed with JAX
compiles for it and raises what the chip's compiler would raise (a block
that does not tile, more SMEM or VMEM than a kernel may use).  Nothing
runs, so these tests say nothing about results or times.  Shapes are at
the service's default block: the fleet tick over one tenant of each
§5.1 testbed model (~348M fp32 elements, wider than the mix
``chip_smoke.py`` fits on one chip, so its SMEM tables bound the
smoke's), a VGG19-sized shard, a relayout of three state leaves.

The topology is described inside a module-scoped fixture, never while a
module is imported: one process at a time may load the TPU library, and
the test workers import every test file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_workloads import MODEL_TENSORS
from repro.core import ParameterService
from repro.kernels.agg_adam import kernel as agg_kernel
from repro.kernels.relayout import kernel as relayout_kernel

BLOCK = ParameterService().plan_pad_to
SMOKE_MIX = ("vgg19", "bert", "awd-lm", "alexnet")


def _blocks(model):
    return -(-sum(n for _, n in MODEL_TENSORS[model]) // BLOCK)


FLEET_BLOCKS = sum(_blocks(m) for m in SMOKE_MIX)  # ~21.2k at 16384
VGG_BLOCKS = _blocks("vgg19")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out.
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("workers", [0, 2])
def test_fused_fleet_tick_compiles_at_smoke_width(one_chip, no_cache,
                                                  workers):
    """The fleet tick's kernel over the whole smoke mix, with one gradient
    and with a W=2 worker stack: the per-job hyperparameters sit in SMEM
    and the two block tables fit in SMEM beside them."""
    n = FLEET_BLOCKS * BLOCK
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((workers, n) if workers else (n,),
                             jnp.float32, sharding=one_chip)
    hp = jax.ShapeDtypeStruct((len(SMOKE_MIX), agg_kernel.HP_COLS),
                              jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((FLEET_BLOCKS,), jnp.int32,
                                 sharding=one_chip)
    _compile(lambda p, g, mu, nu, hp, bi, js:
             agg_kernel.aggregate_adam_multijob_fused(
                 p, g, mu, nu, hp, bi, js, block=BLOCK),
             vec, g, vec, vec, hp, table, table)


def test_block_update_compiles_at_vgg19_shard(one_chip, no_cache):
    """The per-job block update (the runtime's step path) over a shard
    holding VGG19, with the packed parameters in hand."""
    m = VGG_BLOCKS * BLOCK
    vec = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((VGG_BLOCKS,), jnp.int32,
                                 sharding=one_chip)
    _compile(lambda p, g, mu, nu, c, bi: agg_kernel.aggregate_adam_blocks(
        p, g, mu, nu, c, bi, lr=1e-3, block=BLOCK),
        vec, vec, vec, vec, count, table)


def test_relayout_compiles_three_leaves(one_chip, no_cache):
    """The replan relayout over flat/mu/nu of a fleet-wide space, moving
    a VGG19's worth of blocks."""
    base = jax.ShapeDtypeStruct((FLEET_BLOCKS * BLOCK,), jnp.float32,
                                sharding=one_chip)
    staged = jax.ShapeDtypeStruct((VGG_BLOCKS * BLOCK,), jnp.float32,
                                  sharding=one_chip)
    dst = jax.ShapeDtypeStruct((VGG_BLOCKS,), jnp.int32, sharding=one_chip)
    _compile(lambda b, s, d: relayout_kernel.relayout_scatter(
        b, s, d, block=BLOCK), (base,) * 3, (staged,) * 3, dst)


@pytest.mark.parametrize("kernel", ["fused", "relayout"])
def test_untileable_block_raises_off_interpret(kernel):
    """A block the chip cannot tile raises before lowering instead of
    reaching the compiler (or a fallback)."""
    block, n = 128, 4
    vec = jnp.zeros((n * block,), jnp.float32)
    table = jnp.arange(n, dtype=jnp.int32)
    with pytest.raises(ValueError, match="does not tile"):
        if kernel == "fused":
            agg_kernel.aggregate_adam_multijob_fused(
                vec, vec, vec, vec,
                jnp.zeros((1, agg_kernel.HP_COLS), jnp.float32), table,
                jnp.zeros((n,), jnp.int32), block=block)
        else:
            relayout_kernel.relayout_scatter([vec], [vec], table,
                                             block=block)
