"""The dropless mixture reads each expert's weights in place out of the
``[L, E, ...]`` stacks: the same numbers as reading one layer's slab, and
no layer scan that slices the stacks."""
import dataclasses

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.models import moe as moe_mod

MOE_ARCHS = ["granite-moe-3b-a800m", "dbrx-132b"]
N_LAYERS = 3


@pytest.fixture(scope="module", params=MOE_ARCHS)
def moe_setup(request):
    cfg = configs.reduced_config(configs.get_config(request.param),
                                 n_layers=N_LAYERS)
    return cfg, M.init(cfg, jax.random.key(0))


def _stacks(params):
    return {n: params["layers"]["moe"][n] for n in moe_mod.EXPERT_WEIGHTS}


@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_dropless_reads_a_layer_in_place(moe_setup, layer):
    """Layer ``l`` of the whole stacks, with ``l`` traced as the layer scan
    passes it, is bit-identical to the same call on ``stacks[l:l+1]`` at
    layer 0, the way a caller holding one layer passes it."""
    cfg, params = moe_setup
    router = params["layers"]["moe"]["router"][layer]
    stacks = _stacks(params)
    x = jax.random.normal(jax.random.key(layer), (3, 5, cfg.d_model),
                          jnp.bfloat16)
    kw = dict(n_experts=cfg.n_experts, top_k=cfg.top_k, dropless=True)

    @jax.jit
    def whole(x, layer):
        return moe_mod.moe_apply({"router": router, **stacks}, x,
                                 layer=layer, **kw)

    one = {n: w[layer:layer + 1] for n, w in stacks.items()}
    y, aux = whole(x, jnp.int32(layer))
    y1, aux1 = jax.jit(lambda x: moe_mod.moe_apply(
        {"router": router, **one}, x, layer=0, **kw))(x)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y1, np.float32))
    np.testing.assert_array_equal(np.asarray(aux), np.asarray(aux1))


def _scans(jaxpr):
    """Every scan in ``jaxpr``, nested ones too, as (consts, xs) avals."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            n_c, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
            avals = [v.aval for v in eqn.invars]
            yield avals[:n_c], avals[n_c + n_carry:]
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if isinstance(sub, jex_core.Jaxpr):
                yield from _scans(sub)


def _entry_points(cfg, params):
    tok = jnp.zeros((2, 1), jnp.int32)
    toks = jnp.zeros((2, 4), jnp.int32)
    pool = {"k": jnp.zeros((cfg.n_layers, cfg.n_kv_heads, 4, 4,
                            cfg.head_dim), jnp.bfloat16)}
    pool["v"] = pool["k"]
    table = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    cache = M.init_cache(cfg, 2, 8)
    return {
        "forward": (lambda p: M.forward(p, cfg, toks), (params,)),
        "forward_prefill": (lambda p: M.forward_prefill(p, cfg, toks),
                            (params,)),
        "decode_step": (lambda p, c: M.decode_step(p, cfg, tok, c, 1),
                        (params, cache)),
        "decode_step_paged": (lambda p: M.decode_step_paged(
            p, cfg, tok, pool, table, pos, page_size=4), (params,)),
    }


@pytest.mark.parametrize("entry", ["forward", "forward_prefill",
                                   "decode_step", "decode_step_paged"])
def test_layer_scan_closes_over_the_expert_stacks(moe_setup, entry):
    """The layer scan takes the expert stacks as loop-invariant operands,
    never as scanned inputs: a scanned stack is sliced into an ``[E, ...]``
    slab a step, which the nested expert loop then copies whole."""
    cfg, params = moe_setup
    fn, args = _entry_points(cfg, params)[entry]
    stack_shapes = {w.shape for w in _stacks(params).values()}
    scans = list(_scans(jax.make_jaxpr(fn)(*args).jaxpr))
    for _, xs in scans:
        assert not stack_shapes & {a.shape for a in xs}, xs
    assert any(stack_shapes <= {a.shape for a in consts}
               for consts, _ in scans)


@pytest.mark.parametrize("case", ["dense", "moe-capacity", "moe-dropless"])
def test_split_experts_keys_on_a_dropless_mixture(case):
    """Only a dropless mixture's stacks leave the scan: a dense layer and
    the capacity path scan their layers as they are."""
    arch = "qwen3-0.6b" if case == "dense" else "granite-moe-3b-a800m"
    cfg = configs.reduced_config(configs.get_config(arch))
    cfg = dataclasses.replace(cfg, moe_dropless=case == "moe-dropless")
    layers = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))["layers"]
    scanned, experts, ids = M._split_experts(layers, cfg.n_layers,
                                             cfg.moe_dropless)
    if case != "moe-dropless":
        assert scanned is layers and experts is None and ids is None
        return
    assert set(experts) == set(moe_mod.EXPERT_WEIGHTS)
    assert set(scanned["moe"]) == {"router"}
    assert ids.shape == (cfg.n_layers,)
