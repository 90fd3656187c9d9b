"""Real-size compiles for a TPU v5e chip, with no chip attached.

The TPU compiler ships with JAX and compiles for a chip that is described
rather than present.  Each test lowers a program of the main path at the
width it runs at on the chip, with ``interpret=False`` for the Pallas
kernels, and compiles it.  What the chip's compiler refuses (a block not
aligned to the tiling, an unsupported primitive in a kernel, a program that
does not fit the device) then fails here.  Nothing runs, so neither results
nor times are checked.

The topology is described in a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.  All such compiles live in this one
file so that one worker loads the library.  The persistent compilation
cache is off around them: an entry written for a described chip cannot be
read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.cachesim import api
from repro.kernels.capped_simplex.ops import fused_ogb_update
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.prefix_tree.kernel import bucket_masses, block_segment_sums
from repro.kernels.prefix_tree.ops import tree_sizes
from repro.cachesim.tree_engines import OGB_TREE_BUCKETS, OGB_TREE_RADIX

WINDOW = 1000


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # reprolint: allow(broad-except) no TPU compiler here: skip
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _carry_shapes(pd, n: int, c: int):
    init = lambda: pd.init(n, c, seed=0, eta=0.05, horizon=20_000_000)
    if pd.kind == "ogb_tree":
        # its init builds the bucket trees on the host, which eval_shape
        # cannot trace: build the carry and keep its shapes
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), init()
        )
    return jax.eval_shape(init)


@pytest.mark.parametrize("n", [1_000_000, 10_000_000])
@pytest.mark.parametrize("kind", ["ogb", "ogb_tree"])
def test_scan_compiles(kind, n, one_chip):
    pd = api.policy_def(kind)
    carry = _on(one_chip, _carry_shapes(pd, n, n // 20))
    chunks = jax.ShapeDtypeStruct((20_000, WINDOW), jnp.int32,
                                  sharding=one_chip)
    compiled = api._scan_jit(pd.step).lower(carry, chunks).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_fused_ogb_update_compiles(one_chip):
    n = 10_000_000
    f = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = fused_ogb_update.lower(
        f, f, eta=0.01, capacity=n / 20, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_segment_sums_compiles(one_chip):
    # every build level of the ogb_tree bucket trees
    sizes = tree_sizes(OGB_TREE_BUCKETS, OGB_TREE_RADIX)
    for child, out in zip(sizes, sizes[1:]):
        x = jax.ShapeDtypeStruct((child,), jnp.float32, sharding=one_chip)
        compiled = jax.jit(
            lambda v, out=out: block_segment_sums(
                v, out, OGB_TREE_RADIX, interpret=False
            )
        ).lower(x).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_bucket_masses_compiles(one_chip):
    v = jax.ShapeDtypeStruct((OGB_TREE_BUCKETS,), jnp.float32,
                             sharding=one_chip)
    taus = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda c, s, t: bucket_masses(c, s, t, interpret=False)
    ).lower(v, v, taus).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles(one_chip):
    # one decode step of an 8-sequence batch over an 8k-token cache at the
    # attention width of a 12B model: 32 query heads, 8 KV heads, D = 128
    b, h, hkv, d, s = 8, 32, 8, 128, 8192
    q = jax.ShapeDtypeStruct((b, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    compiled = decode_attention.lower(
        q, kv, kv, lengths, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()

