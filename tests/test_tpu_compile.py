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
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import flatbuf
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.gossip_mix import ops as gm_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.models import model as M
from repro.serve import ServeEngine

QWEN3 = configs.get_config("qwen3-0.6b")
# granite's published widths, served in bf16; two layers keep the compile
# short and still give the layer loop a stacked operand to slice
GRANITE = dataclasses.replace(configs.get_config("granite-moe-3b-a800m"),
                              n_layers=2, param_dtype=jnp.bfloat16,
                              remat=False)


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


def _granite_serving_hlo(one_chip, exe):
    """The engine's ``serve_decode`` (8 rows, page 16) or ``serve_prefill``
    (1 x 512) at granite's widths, compiled for the described chip."""
    cfg = GRANITE
    eng = ServeEngine(cfg, None, n_pages=64, page_size=16, max_seq=2560,
                      max_batch=8)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _arg(a.shape, a.dtype, one_chip), t)
    params = on_chip(jax.eval_shape(lambda: M.init(cfg, jax.random.key(0))))
    pool = on_chip(eng.pool)
    i32 = lambda *shape: _arg(shape, np.int32, one_chip)  # noqa: E731
    if exe == "serve_decode":
        lowered = eng._decode_exe(8).lower(
            params, i32(8, 1), pool, i32(8, eng.pmax), i32(8))
    else:
        lowered = eng._prefill_exe(1, 512).lower(
            params, i32(1, 512), i32(1, 512), pool, i32(1, 512),
            i32(1, 512), i32(1))
    return lowered.compile().as_text()


@pytest.mark.parametrize("exe", ["serve_decode", "serve_prefill"])
def test_serving_reads_expert_weights_in_place(one_chip, exe):
    """The dropless mixture reads each expert out of the ``[L, E, d, f]``
    stacks at (layer, expert).  A layer loop that sliced the layer's
    ``[E, d, f]`` slab would make it the operand of the nested expert
    loop, and XLA copies that slab whole every step (a third of a decode
    step at these widths on v5e)."""
    E, d, f = GRANITE.n_experts, GRANITE.d_model, GRANITE.d_ff
    slab = re.compile(
        rf"= bf16\[({E},{d},{f}|{E},{f},{d})\]\{{[^}}]*\}} "
        r"(dynamic-slice|copy|fusion)\(")
    text = _granite_serving_hlo(one_chip, exe)
    assert f"bf16[{GRANITE.n_layers},{E},{d},{f}]" in text
    copies = [ln.strip()[:160] for ln in text.splitlines()
              if slab.search(ln)]
    assert not copies, copies
