"""Public model API: init / forward / prefill / decode_step / loss.

Batch dict conventions (all global shapes):
  train:   {"tokens": (B, S) i32, "labels": (B, S) i32}    — token LMs
           {"embeds": (B, S, d) bf16, "labels": (B, S)}    — vlm/audio stubs
  prefill: {"tokens" | "embeds"}                           — returns cache
  decode:  {"tokens": (B, 1) i32, "pos": () i32, cache}    — one step
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as tf
from repro.models.layers import (cross_entropy, dense_init, embed_init,
                                 embed_tokens, lm_logits, rms_norm)
from repro.sharding import shard

Params = Dict[str, Any]


# --------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------- #
def init_params(cfg, key) -> Params:
    k_embed, k_stack, k_head = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    stack = tf.get_stack(cfg)
    p: Params = {
        "embed": {"table": embed_init(k_embed, (cfg.padded_vocab,
                                                cfg.d_model), dt)},
        "stack": stack.init(k_stack, cfg),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": dense_init(k_head, (cfg.d_model,
                                                 cfg.padded_vocab), dt)}
    return p


def param_specs(cfg) -> Params:
    stack = tf.get_stack(cfg)
    s: Params = {
        "embed": {"table": ("vocab", "embed")},
        "stack": _with_stack_lead(cfg, stack.specs(cfg)),
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = {"w": ("embed", "vocab")}
    return s


def _with_stack_lead(cfg, specs):
    """Stack specs get a leading (scan) axis of None; hybrid/xlstm specs
    already encode their own leading axes except the shared block."""
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        return jax.tree.map(lambda n: (None,) + n, specs,
                            is_leaf=lambda x: isinstance(x, tuple))
    if cfg.family == "hybrid":
        lead = lambda t: jax.tree.map(lambda n: (None,) + n, t,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return {"groups": lead(specs["groups"]), "shared": specs["shared"]}
    if cfg.family == "ssm":
        lead = lambda t: jax.tree.map(lambda n: (None,) + n, t,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return {"groups": lead(specs["groups"])}
    raise ValueError(cfg.family)


# --------------------------------------------------------------------- #
# forward paths
# --------------------------------------------------------------------- #
def _embed_in(cfg, params, batch, dtype):
    if "embeds" in batch:
        h = batch["embeds"].astype(dtype)
    else:
        h = embed_tokens(params["embed"]["table"], batch["tokens"], dtype)
    if cfg.scale_embed:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return shard(h, "batch", "seq", None)


def _head(cfg, params, h):
    if cfg.tie_embeddings:
        w = params["embed"]["table"].T
    else:
        w = params["lm_head"]["w"]
    return lm_logits(h, w, cfg.final_softcap)


def forward(cfg, params, batch, mode: str = "train",
            cache=None) -> Tuple[jax.Array, Any, Dict]:
    """Returns (hidden or logits inputs, cache, aux). Hidden is post-norm."""
    dtype = jnp.dtype(cfg.compute_dtype)
    h = _embed_in(cfg, params, batch, dtype)
    B, S = h.shape[0], h.shape[1]
    if mode == "decode":
        pos = batch["pos"]
        if cfg.decode_per_slot:
            positions = pos.reshape(B, 1).astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(pos[None, None],
                                         (B, S)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                     (B, S))
    stack = tf.get_stack(cfg)
    h, new_cache, aux = stack.apply(params["stack"], cfg, h,
                                    positions=positions, mode=mode,
                                    cache=cache)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, new_cache, aux


def loss_fn(cfg, params, batch) -> Tuple[jax.Array, Dict]:
    h, _, aux = forward(cfg, params, batch, mode="train")
    logits = _head(cfg, params, h)
    loss, acc = cross_entropy(logits, batch["labels"], cfg.vocab_size)
    metrics = {"loss": loss, "accuracy": acc}
    if aux and "aux_loss" in aux:
        metrics["moe_aux"] = aux["aux_loss"]
        metrics["moe_drop"] = aux.get("drop_frac", jnp.zeros(()))
        loss = loss + cfg.router_aux_weight * aux["aux_loss"]
    metrics["total_loss"] = loss
    return loss, metrics


def prefill(cfg, params, batch) -> Tuple[jax.Array, Any]:
    """Returns (last-token logits (B, vocab), cache)."""
    h, cache, _ = forward(cfg, params, batch, mode="prefill")
    logits = _head(cfg, params, h[:, -1:, :])[:, 0, :]
    return logits, cache


def decode_step(cfg, params, cache, tokens: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, Any]:
    """tokens: (B, 1); pos: scalar i32 (position being written), or
    (B,) per-slot positions when cfg.decode_per_slot is set."""
    batch = {"tokens": tokens, "pos": pos}
    h, new_cache, _ = forward(cfg, params, batch, mode="decode", cache=cache)
    logits = _head(cfg, params, h)[:, 0, :]
    return logits, new_cache


def init_cache(cfg, batch: int, cache_len: int,
               dtype=jnp.bfloat16):
    return tf.get_stack(cfg).init_cache(cfg, batch, cache_len, dtype)


def cache_specs(cfg):
    return tf.get_stack(cfg).cache_specs(cfg)


# --------------------------------------------------------------------- #
# parameter counting (roofline MODEL_FLOPS)
# --------------------------------------------------------------------- #
def count_params(cfg, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    if active_only and cfg.num_experts:
        per_expert = 3 * cfg.d_model * cfg.d_ff
        inactive = max(cfg.n_held_experts - cfg.top_k, 0) * per_expert
        total -= (cfg.num_layers - cfg.first_dense_layers) * inactive
    return int(total)


def count_nonembedding_params(cfg, active_only: bool = False) -> int:
    n = count_params(cfg, active_only)
    n -= cfg.padded_vocab * cfg.d_model  # input table (lookup, not matmul)
    return int(n)
