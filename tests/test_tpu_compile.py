"""Compile rehearsal: the serving path's Pallas kernels and its pruning
decision, compiled for a described (not attached) TPU v5e at the sizes
the chip runs, with the installed TPU compiler. Nothing runs, so this
checks what the chip's compiler would refuse (alignment, VMEM,
unsupported ops) and that each kernel's program really contains the
Mosaic kernel — not results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.query import (MIDGRID_BLOCK_ROWS, PHASE1_BLOCKS,
                              compact_survivors, probe_pick, probe_theta,
                              prune_decide)
from repro.kernels.bm25_blockmax.kernel import (bm25_blocks_compact_pallas,
                                                bm25_blocks_midgrid_pallas,
                                                bm25_blocks_pallas)
from repro.kernels.postings_pack.kernel import pack_pallas, unpack_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    """Compile ``fn`` at ``shapes`` for the chip; returns the HLO text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"
    return text


def _blocks(sh, nb):
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=sh)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sh)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sh)
    return (u32(nb, 128), i32(nb), i32(nb), u32(nb, 128), i32(nb),
            f32(nb), i32(nb))


@pytest.mark.parametrize("nb", [128, 4096, 3000])
def test_skip_kernel_compiles_for_v5e(one_chip, nb):
    """Survivor buckets (pow2, 128..4096 blocks) and an unbucketed
    exhaustive candidate grid."""
    _compile(lambda *a: bm25_blocks_pallas(*a, interpret=False),
             *_blocks(one_chip, nb))


def test_exhaustive_batched_grid_compiles_for_v5e(one_chip):
    """The dense oracle vmaps the scorer over a query batch: 32 queries
    x (4 terms x 512 blocks) candidates."""
    B, nb = 32, 4 * 512
    shapes = [jax.ShapeDtypeStruct((B,) + s.shape, s.dtype,
                                   sharding=one_chip)
              for s in _blocks(one_chip, nb)]
    _compile(jax.vmap(lambda *a: bm25_blocks_pallas(*a, interpret=False)),
             *shapes)


def test_midgrid_kernel_compiles_for_v5e(one_chip):
    nb = 1024
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    _compile(lambda *a: bm25_blocks_midgrid_pallas(
        *a, k=10, block_rows=MIDGRID_BLOCK_ROWS, interpret=False),
        *_blocks(one_chip, nb), i32(nb), f32(nb), f32(1, 128), f32())


def test_compact_kernel_compiles_for_v5e(one_chip):
    """A real segment's compact words (~3M plane rows -> 96k rows of 128
    words, left in HBM) and a 4096-block survivor bucket."""
    nb, m = 4096, 96 * 1024
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    _compile(lambda *a: bm25_blocks_compact_pallas(*a, interpret=False),
             u32(m, 128), i32(nb), i32(nb), i32(nb), u32(m, 128), i32(nb),
             i32(nb), f32(nb), i32(nb))


def test_pack_and_unpack_compile_for_v5e(one_chip):
    """Whole-segment packs: >100k blocks, not a multiple of the tile."""
    nb = (1 << 17) + 5
    u32 = jax.ShapeDtypeStruct((nb, 128), jnp.uint32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=one_chip)
    _compile(lambda d: pack_pallas(d, interpret=False), u32)
    _compile(lambda p, bw: unpack_pallas(p, bw, interpret=False), u32, i32)


@pytest.mark.parametrize("bmw", [True, False])
def test_pruning_decision_compiles_for_v5e(one_chip, bmw):
    """The device pruning decision at the serving cell's shapes: a batch
    of 32 queries x 8 terms x 512 candidate blocks, k=1000, a 4096-block
    survivor bucket. Plain XLA programs, no kernel."""
    B, Q, MB, k, P1 = 32, 8, 512, 1000, PHASE1_BLOCKS
    sd = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    f32, i32 = (lambda *s: sd(jnp.float32, *s)), (lambda *s: sd(jnp.int32, *s))
    b1 = lambda *s: sd(jnp.bool_, *s)
    ub, in_term, blk = f32(B, Q, MB), b1(B, Q, MB), i32(B, Q, MB)
    probe_pick.lower(ub, in_term, blk, ub, n_phase1=P1).compile()
    probe_theta.lower(f32(B, k), f32(B)).compile()
    prune_decide.lower(ub, in_term, blk, blk, f32(B), i32(B, P1), b1(B, P1),
                       bmw=bmw).compile()
    compact_survivors.lower(i32(B, Q * MB), blk, ub, f32(B, Q * MB),
                            bucket=4096).compile()
