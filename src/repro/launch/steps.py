"""Train / prefill / serve step builders + input_specs for the dry-run.

``input_specs`` follows the ShapeDtypeStruct pattern: weak-type-correct,
shardable stand-ins for every model input; nothing is allocated.

Input shapes (assignment):
  train_4k     seq=4096    global_batch=256   -> train_step (DmSGD gossip)
  prefill_32k  seq=32768   global_batch=32    -> prefill_step
  decode_32k   seq=32768   global_batch=128   -> serve_step (1 new token)
  long_500k    seq=524288  global_batch=1     -> serve_step, sub-quadratic
               (SSM/hybrid native; full-attention archs take the
               sliding-window override, see DESIGN §long_500k)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import optim as optim_mod
from repro.models import model as M

PyTree = Any

__all__ = ["SHAPES", "shape_cfg", "input_specs", "make_train_step",
           "make_prefill_step", "make_serve_step", "train_loss_fn",
           "LONG_WINDOW"]

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq=524288, global_batch=1),
}

LONG_WINDOW = 8192  # sliding-window override for full-attention @ long_500k


def shape_cfg(cfg: M.ModelConfig, shape_name: str) -> M.ModelConfig:
    """Apply per-shape config overrides (long_500k sliding window)."""
    if shape_name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return dataclasses.replace(cfg, attention_override_window=LONG_WINDOW)
    return cfg


def _token_struct(cfg: M.ModelConfig, lead: tuple, seq: int):
    shp = lead + (seq,)
    if cfg.family == "audio":
        shp = shp + (cfg.n_codebooks,)
    return jax.ShapeDtypeStruct(shp, jnp.int32)


def input_specs(cfg: M.ModelConfig, shape_name: str, *, nodes: int = 1):
    """ShapeDtypeStruct stand-ins for every input of the step function."""
    info = SHAPES[shape_name]
    seq, gb = info["seq"], info["global_batch"]
    adt = cfg.activation_dtype
    if info["kind"] == "train":
        pnb = gb // nodes
        if pnb < 1:
            raise ValueError(
                f"global_batch {gb} < nodes {nodes}: the decentralized "
                "layout needs at least one sequence per node")
        out = {"tokens": _token_struct(cfg, (nodes, pnb), seq)}
        if cfg.family == "vlm":
            out["image_embeds"] = jax.ShapeDtypeStruct(
                (nodes, pnb, cfg.n_image_tokens, cfg.d_model), adt)
        return out
    if info["kind"] == "prefill":
        out = {"tokens": _token_struct(cfg, (gb,), seq)}
        if cfg.family == "vlm":
            out["image_embeds"] = jax.ShapeDtypeStruct(
                (gb, cfg.n_image_tokens, cfg.d_model), adt)
        return out
    # decode: one new token, KV/SSM cache covering `seq`
    out = {"token": _token_struct(cfg, (gb,), 1),
           "idx": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.family == "vlm":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (gb, cfg.n_image_tokens, cfg.d_model), adt)
    return out


def cache_len_for(cfg: M.ModelConfig, shape_name: str) -> int:
    seq = SHAPES[shape_name]["seq"]
    if cfg.attention_override_window is not None:
        return min(seq, cfg.attention_override_window)
    return seq


def cache_struct(cfg: M.ModelConfig, shape_name: str):
    """eval_shape'd decode cache (no allocation)."""
    gb = SHAPES[shape_name]["global_batch"]
    cl = cache_len_for(cfg, shape_name)
    return jax.eval_shape(lambda: M.init_cache(cfg, gb, cl))


# ---------------------------------------------------------------------------
# Loss / steps
# ---------------------------------------------------------------------------

def train_loss_fn(params, cfg: M.ModelConfig, tokens, image_embeds=None,
                  aux_weight: float = 0.01):
    """Next-token CE (labels = tokens shifted left), + MoE aux loss.

    Sharding-native: no reshape across sharded batch dims and no gather over
    the vocab-sharded logits -- the label logit is extracted with an
    iota==label masked reduction, so the vocab axis stays sharded and only
    per-token scalars cross the mesh (tiny all-reduces)."""
    if cfg.n_experts and cfg.moe_dropless:
        # training uses the GShard capacity dispatch (active-param FLOPs);
        # the dropless exact mixture is the serving/eval path.
        cfg = dataclasses.replace(cfg, moe_dropless=False)
    # device scope: the backward pass of these ops is named
    # ``transpose(jvp(forward))``, the remat recompute inside it
    # ``.../rematted_computation/...``
    with jax.named_scope("forward"):
        logits, aux = M.forward(params, cfg, tokens,
                                image_embeds=image_embeds)
        labels = jnp.roll(tokens, -1, axis=1)
        lo = logits.astype(jnp.float32)        # (..., V), V possibly sharded
        mx = jax.lax.stop_gradient(jnp.max(lo, axis=-1, keepdims=True))
        lse = jnp.squeeze(mx, -1) + jnp.log(jnp.sum(jnp.exp(lo - mx),
                                                    axis=-1))
        col = jax.lax.broadcasted_iota(jnp.int32, lo.shape, lo.ndim - 1)
        label_logit = jnp.sum(jnp.where(col == labels[..., None], lo, 0.0),
                              axis=-1)
        ce = (lse - label_logit).mean()
        return ce + aux_weight * aux


def make_train_step(cfg: M.ModelConfig,
                    opt: optim_mod.DecentralizedOptimizer,
                    *, micro_batch: int | None = None,
                    grads_dtype=jnp.float32):
    """Returns ``train_step(mix, params, opt_state, batch, lr)``.

    ``mix`` is the realization-bound gossip executor (the first, Python-
    level argument): :class:`repro.core.plan.GossipPlan` compiles one
    executable per distinct realization-IR node, closing over that
    realization's ``mix`` -- ``Shifts``/``Matching`` rounds bake their
    (explicit-pairs) collective-permutes into HLO, time-varying ``Dense``
    rounds receive ``W^{(k)}`` as a traced argument inside the plan's
    shared executable, and ``Identity`` off-steps (``gossip(every=k)``)
    share one no-communication executable.

    Gradients are computed per node (vmap over the leading node axis) with
    optional microbatch accumulation, then fed to the decentralized
    optimizer -- partial averaging happens inside ``opt.update_with_mix``.

    For an OVERLAPPED optimizer (``gossip(..., overlap=True)``), ``mix``
    is the plan's :class:`repro.core.plan.OverlapIO` bundle and the step
    is pipelined: the previous step's payload permute reads only the
    in-flight buffer in ``opt_state.buf``, so it carries no dependency on
    this step's forward/backward and XLA hides it under the compute;
    gradients land on the pre-mix params (the delayed-mix recursion).
    """

    def per_node_grads(p, tokens, image_embeds):
        if micro_batch is None or micro_batch >= tokens.shape[0]:
            loss, g = jax.value_and_grad(train_loss_fn)(
                p, cfg, tokens, image_embeds)
            return loss, g
        nm = tokens.shape[0] // micro_batch
        toks = tokens.reshape((nm, micro_batch) + tokens.shape[1:])
        imgs = (image_embeds.reshape((nm, micro_batch)
                                     + image_embeds.shape[1:])
                if image_embeds is not None else None)

        def body(carry, mb):
            acc_loss, acc_g = carry
            tok = mb[0]
            img = mb[1] if imgs is not None else None
            loss, g = jax.value_and_grad(train_loss_fn)(p, cfg, tok, img)
            acc_g = jax.tree.map(
                lambda a, b: a + b.astype(grads_dtype) / nm, acc_g, g)
            return (acc_loss + loss / nm, acc_g), None

        g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, grads_dtype), p)
        xs = (toks, imgs) if imgs is not None else (toks,)
        (loss, g), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), g0), xs)
        return loss, g

    def train_step(mix, params, opt_state, batch, lr):
        tokens = batch["tokens"]
        image_embeds = batch.get("image_embeds")
        if image_embeds is None:
            losses, grads = jax.vmap(
                lambda p, t: per_node_grads(p, t, None))(params, tokens)
        else:
            losses, grads = jax.vmap(per_node_grads)(params, tokens,
                                                     image_embeds)
        if opt.overlap:
            with jax.named_scope("optimizer"):
                new_params, new_state = opt.update_pipelined(
                    params, opt_state, grads, lr, mix)
        else:
            aux = None
            if getattr(opt, "has_runtime_gossip", False):
                # runtime-valued gossip reads per-node signals: the fresh
                # losses (AL-DSGD weights) and any deadline/straggler flags
                # the data pipeline attached to the batch
                aux = {"loss": losses}
                for key in ("alive", "comm"):
                    if key in batch:
                        aux[key] = batch[key]
            with jax.named_scope("optimizer"):
                new_params, new_state = opt.update_with_mix(
                    params, opt_state, grads, lr, mix, aux=aux)
        return new_params, new_state, losses.mean()

    return train_step


def make_prefill_step(cfg: M.ModelConfig):
    def prefill_step(params, batch):
        logits, _ = M.forward(params, cfg, batch["tokens"],
                              image_embeds=batch.get("image_embeds"))
        # serving prefill: return last-position logits (next-token dist)
        return logits[:, -1, :] if cfg.family != "audio" \
            else logits[:, -1, :, :]
    return prefill_step


def make_serve_step(cfg: M.ModelConfig):
    def serve_step(params, cache, batch):
        logits, new_cache = M.decode_step(
            params, cfg, batch["token"], cache, batch["idx"],
            image_embeds=batch.get("image_embeds"))
        return logits, new_cache
    return serve_step
