"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (unaligned blocks, too much VMEM, HBM overflow).  Interpret-mode
tests cannot see those faults.  The topology is described inside a module
fixture -- never at import -- because only one process may load the TPU
library at a time; the tests skip where it cannot be described.  The
persistent compilation cache stays off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import flatbuf
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.gossip_mix import ops as gm_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.models import model as M

QWEN3 = configs.get_config("qwen3-0.6b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 64 * 1024), (1, 64 * 1024)],
                         ids=["8-rows", "1-row"])
def test_gossip_mix_compiles_for_v5e(one_chip, shape, dtype):
    x = _arg(shape, dtype, one_chip)
    fn = jax.jit(lambda x, r: gm_ops.gossip_mix(
        x, [r], w_self=0.5, ws=(0.5,), interpret=False))
    compiled = fn.lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gossip_mix_on_a_node_payload_copies_nothing(one_chip):
    """One qwen3 node's packed f32 parameters, as the shard-native round
    hands them to the combine: a (1, B) buffer.  The kernel must stream it
    in place -- a reshape to (B / 1024, 1024) is a relayout on TPU and
    cost three payload-sized temporaries."""
    shapes = jax.eval_shape(lambda: M.init(QWEN3, jax.random.key(0)))
    node = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), shapes)
    (group,) = flatbuf.layout_of(node, pad_multiple=1).groups
    x = _arg((1, group.padded), jnp.float32, one_chip)
    fn = jax.jit(lambda x, r: gm_ops.gossip_mix(
        x, [r], w_self=0.5, ws=(0.5,), interpret=False))
    mem = fn.lower(x, x).compile().memory_analysis()
    assert mem.temp_size_in_bytes < group.padded * 4 // 100, mem


@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_attention_compiles_at_qwen3_serving_widths(one_chip,
                                                          page_size):
    B, n_pages, Pmax = 4, 64, 4
    H, Kv, D = QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.head_dim
    q = _arg((B, H, D), jnp.bfloat16, one_chip)
    pool = _arg((Kv, n_pages, page_size, D), jnp.bfloat16, one_chip)
    table = _arg((B, Pmax), jnp.int32, one_chip)
    lengths = _arg((B,), jnp.int32, one_chip)
    compiled = pa_ops.paged_attention.lower(
        q, pool, pool, table, lengths, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [16, 32, 512])
def test_flash_attention_compiles_at_qwen3_prefill_widths(one_chip, seq):
    H, Kv, D = QWEN3.n_heads, QWEN3.n_kv_heads, QWEN3.head_dim
    q = _arg((4, seq, H, D), jnp.bfloat16, one_chip)
    kv = _arg((4, seq, Kv, D), jnp.bfloat16, one_chip)
    compiled = fa_ops.flash_attention.lower(
        q, kv, kv, causal=True, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
