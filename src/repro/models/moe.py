"""Top-k token-choice MoE with sort-based capacity dispatch.

Design: the usual one-hot dispatch einsum (GShard) materializes a
(tokens × experts × capacity) tensor — hopeless at 1M tokens. We instead
sort the (token, choice) assignments by expert id and gather each
expert's first-C tokens into a dense (E, C, d) block, so compute scales
with *active* FLOPs (tokens · top_k · d · f), the quantity the roofline
is judged against. Static shapes throughout; overflow tokens are dropped
(standard capacity-factor semantics) and counted in aux metrics.

Sharding: experts -> "exp" (model axis, EP), capacity -> "cap" (data
axis), so the (E, C, d) blocks are 2-D sharded.

Routing (``route``) is softmax top-k, or DeepSeek-V3's sigmoid scores
with a selection-only correction bias (``noaux_tc``), the chosen
weights normalised and scaled. A chip of an expert-parallel group
(``cfg.held_experts``) routes over every expert and computes only its
held experts' share of the result, drop-free (``held_dispatch``): each
held expert gets a slot for every token, so nothing overflows.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, dense_init
from repro.sharding import shard


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# spread of the seeded ``noaux_tc`` correction bias: the learned
# buffer is not in the published config; small beside the sigmoid
# scores' spacing at the top-k boundary (~1e-2), yet it moves many
# selections, so a path that ignores it does not pass for one that uses it
ROUTER_BIAS_STD = 0.01


def moe_init(key, cfg) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    Eh = cfg.n_held_experts
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": dense_init(ks[0], (d, E), dt, fan_in=d),
        "w1": dense_init(ks[1], (Eh, d, f), dt, fan_in=d),
        "w3": dense_init(ks[2], (Eh, d, f), dt, fan_in=d),
        "w2": dense_init(ks[3], (Eh, f, d), dt, fan_in=f),
    }
    if cfg.router_bias:
        p["router_bias"] = (ROUTER_BIAS_STD * jax.random.normal(
            ks[5], (E,), jnp.float32)).astype(dt)
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w1": dense_init(k1, (d, fs), dt),
            "w3": dense_init(k3, (d, fs), dt),
            "w2": dense_init(k2, (fs, d), dt, fan_in=fs),
        }
    return p


def moe_specs(cfg) -> Dict:
    s = {
        "router": ("embed", None),
        "w1": ("exp", "embed", None),
        "w3": ("exp", "embed", None),
        "w2": ("exp", None, "embed"),
    }
    if cfg.router_bias:
        s["router_bias"] = (None,)
    if cfg.num_shared_experts:
        s["shared"] = {"w1": ("embed", "ff"), "w3": ("embed", "ff"),
                       "w2": ("ff", "embed")}
    return s


def route(cfg, logits: jax.Array, bias=None):
    """Router logits (..., E) f32 → (weights (..., K), expert ids
    (..., K), scores (..., E)). Selection ranks ``scores + bias``; the
    weights are the chosen experts' unbiased scores, normalised over
    the k chosen (DeepSeek-V3's ``norm_topk_prob``, with its 1e-20 in
    the sum), then scaled by ``cfg.router_scale``."""
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score {cfg.router_score!r}")
    choice = scores if bias is None else scores + bias.astype(scores.dtype)
    _, ids = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.router_scale, ids, scores


def held_dispatch(cfg, w: jax.Array, ids: jax.Array):
    """Drop-free dispatch of T tokens' top-k choices to the held
    experts: → (token ids (Eh, T), gates (Eh, T), stats). Expert j's
    slots list the tokens that chose ``held_experts[j]`` first, in
    token order, then padding rows of gate 0; every expert has T slots,
    so no token is dropped. ``stats`` counts, as int32, the
    token-expert pairs on held experts (``assignments``), the rows the
    expert layer computes (``rows``) and the pairs that found no slot
    (``dropped``)."""
    T = ids.shape[0]
    held = jnp.asarray(cfg.held_experts or range(cfg.num_experts),
                       jnp.int32)
    match = ids[None, :, :] == held[:, None, None]          # (Eh, T, K)
    gate = jnp.where(match, w[None], 0.0).sum(-1)           # (Eh, T)
    chosen = match.any(-1)
    order = jnp.argsort(~chosen, axis=-1, stable=True)      # (Eh, T)
    count = chosen.sum(-1)
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < count[:, None]
    gates = jnp.take_along_axis(gate, order, axis=-1) * valid
    assigned = count.sum().astype(jnp.int32)
    stats = {"assignments": assigned,
             "rows": jnp.int32(held.shape[0] * T),
             "dropped": assigned - valid.sum().astype(jnp.int32)}
    return order, gates, stats


def held_combine(y_e: jax.Array, order: jax.Array, gates: jax.Array,
                 T: int) -> jax.Array:
    """Sum each held expert's gated rows back onto their tokens:
    y_e (Eh, T, d) → (T, d) f32."""
    d = y_e.shape[-1]
    y = (y_e.astype(jnp.float32) * gates[..., None]).reshape(-1, d)
    return jax.ops.segment_sum(y, order.reshape(-1), num_segments=T)


def _moe_held(p: Dict, cfg, xt: jax.Array) -> Tuple[jax.Array, Dict]:
    """This chip's share of the layer for xt (T, d): routing over every
    expert, the held experts' SwiGLU on their dispatched tokens."""
    dt = xt.dtype
    T = xt.shape[0]
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dt),
                        preferred_element_type=jnp.float32)
    w, ids, scores = route(cfg, logits, p.get("router_bias"))
    order, gates, stats = held_dispatch(cfg, w, ids)
    x_e = jnp.take(xt, order, axis=0)                       # (Eh, T, d)
    h = jnp.einsum("etd,edf->etf", x_e, p["w1"].astype(dt))
    g = jnp.einsum("etd,edf->etf", x_e, p["w3"].astype(dt))
    h = act_fn(cfg.act)(h) * g
    y_e = jnp.einsum("etf,efd->etd", h, p["w2"].astype(dt))
    y = held_combine(y_e, order, gates, T).astype(dt)
    me = scores.mean(axis=0)
    ce = jax.nn.one_hot(ids[:, 0], cfg.num_experts,
                        dtype=jnp.float32).mean(axis=0)
    aux_loss = cfg.num_experts * jnp.sum(me * ce)
    return y, {"aux_loss": aux_loss,
               "drop_frac": stats["dropped"] / jnp.maximum(
                   stats["assignments"], 1)}


def shared_mlp(p: Dict, cfg, x: jax.Array) -> jax.Array:
    """The shared experts (one SwiGLU of width d_ff x shared count)."""
    dt = x.dtype
    sh = p["shared"]
    hs = act_fn(cfg.act)(jnp.einsum("bsd,df->bsf", x, sh["w1"].astype(dt)))
    hs = hs * jnp.einsum("bsd,df->bsf", x, sh["w3"].astype(dt))
    hs = shard(hs, "batch", None, "ff")
    return jnp.einsum("bsf,fd->bsd", hs, sh["w2"].astype(dt))


def capacity(tokens: int, cfg) -> int:
    c = int(round(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor))
    return max(8, _round_up(c, 8))


def moe_apply(p: Dict, cfg, x: jax.Array) -> Tuple[jax.Array, Dict]:
    """x: (B, S, d) -> (y, aux). Aux carries the load-balance loss.

    With ``cfg.moe_groups = G > 1`` the tokens are split into G
    local-dispatch groups aligned with the DP shards (GShard's G axis):
    routing, capacity gather and combine all stay inside a group, so the
    MoE block emits **no collectives** for dispatch/combine — only the
    expert einsums touch the (model-sharded) weights. Measured on
    dbrx-132b prefill_32k: 65.4 s -> 1.9 s collective term (§Perf B1).
    A chip that holds a slice of the experts (``cfg.held_experts``)
    takes its share drop-free (``_moe_held``).
    """
    B, S, d = x.shape
    G = max(cfg.moe_groups, 1)
    T = B * S
    if cfg.held_experts:
        y, aux = _moe_held(p, cfg, x.reshape(T, d))
        y = y.reshape(B, S, d)
    elif G > 1 and T % G == 0:
        y, aux = _moe_grouped(p, cfg, x.reshape(G, T // G, d))
        y = y.reshape(B, S, d)
    else:
        y, aux = _moe_tokens(p, cfg, x.reshape(T, d), constrain=True)
        y = y.reshape(B, S, d)
    y = shard(y, "batch", "seq", None)

    if cfg.num_shared_experts:
        y = y + shared_mlp(p, cfg, x)
    return y, aux


def _moe_tokens(p: Dict, cfg, xt: jax.Array, *, constrain: bool
                ) -> Tuple[jax.Array, Dict]:
    """Route one token group. xt: (T, d) -> (y (T, d), aux)."""
    dt = xt.dtype
    T, d = xt.shape
    E, K = cfg.num_experts, cfg.top_k
    C = capacity(T, cfg)

    # -- routing -------------------------------------------------------- #
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dt),
                        preferred_element_type=jnp.float32)
    gate_vals, expert_ids, probs = route(cfg, logits,
                                         p.get("router_bias"))  # (T, K)

    # load-balance aux (Switch): E * mean(frac_tokens_e * mean_prob_e)
    me = probs.mean(axis=0)                                       # (E,)
    one_hot_top1 = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)
    aux_loss = E * jnp.sum(me * ce)

    # -- sort-based dispatch -------------------------------------------- #
    flat_e = expert_ids.reshape(-1)                               # (T*K,)
    order = jnp.argsort(flat_e, stable=True)                      # (T*K,)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype),
                              side="left")                        # (E,)
    ends = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype),
                            side="right")
    counts = ends - starts                                        # (E,)
    slot = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (E, C)
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < counts[:, None]
    slot = jnp.where(valid, slot, 0)
    flat_slot = jnp.take(order, slot.reshape(-1), axis=0).reshape(E, C)
    token_ids = flat_slot // K                                    # (E, C)
    choice = flat_slot % K
    # gather each slot's gate value: gate_vals[token_ids, choice]
    gates_ec = gate_vals[token_ids.reshape(-1), choice.reshape(-1)]
    gates_ec = (gates_ec.reshape(E, C) * valid).astype(jnp.float32)

    # -- expert compute -------------------------------------------------- #
    x_e = jnp.take(xt, token_ids.reshape(-1), axis=0).reshape(E, C, d)
    if constrain:
        x_e = shard(x_e, "exp", "cap", None)
    h = jnp.einsum("ecd,edf->ecf", x_e, p["w1"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", x_e, p["w3"].astype(dt))
    h = act_fn(cfg.act)(h) * g
    if constrain:
        h = shard(h, "exp", "cap", None)
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))
    y_e = y_e * gates_ec[..., None].astype(dt)
    if constrain:
        y_e = shard(y_e, "exp", "cap", None)

    # -- combine ---------------------------------------------------------- #
    seg = jnp.where(valid, token_ids, T).reshape(-1)  # invalid -> dropped row
    y = jax.ops.segment_sum(
        y_e.reshape(E * C, d).astype(jnp.float32), seg, num_segments=T + 1
    )[:T].astype(dt)

    dropped = 1.0 - valid.sum() / jnp.maximum(flat_e.shape[0], 1)
    return y, {"aux_loss": aux_loss, "drop_frac": dropped}


def _moe_grouped(p: Dict, cfg, xg: jax.Array) -> Tuple[jax.Array, Dict]:
    """Local-dispatch MoE with an explicit group axis.

    xg: (G, Tg, d), G aligned with the DP shards ("batch"). Routing,
    capacity gather and combine are per-group (vmapped index ops — no
    collectives); the expert einsums carry explicit sharding constraints
    (G->data, E->model) so the only cross-device traffic is the expert
    partial-result reduction XLA emits for the model axis, in bf16.
    """
    dt = xg.dtype
    G, Tg, d = xg.shape
    E, K = cfg.num_experts, cfg.top_k
    C = capacity(Tg, cfg)
    xg = shard(xg, "batch", None, None)

    logits = jnp.einsum("gtd,de->gte", xg, p["router"].astype(dt),
                        preferred_element_type=jnp.float32)
    gate_vals, expert_ids, probs = route(cfg, logits,
                                         p.get("router_bias"))

    me = probs.mean(axis=1)                                  # (G, E)
    ce = jax.nn.one_hot(expert_ids[:, :, 0], E,
                        dtype=jnp.float32).mean(axis=1)
    aux_loss = E * jnp.sum(me * ce, axis=-1).mean()

    def dispatch(flat_e):
        """(Tg*K,) expert ids -> (E, C) slot ids + validity (per group)."""
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        ar = jnp.arange(E, dtype=sorted_e.dtype)
        starts = jnp.searchsorted(sorted_e, ar, side="left")
        counts = jnp.searchsorted(sorted_e, ar, side="right") - starts
        slot = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        valid = jnp.arange(C, dtype=jnp.int32)[None, :] < counts[:, None]
        slot = jnp.where(valid, slot, 0)
        flat_slot = jnp.take(order, slot.reshape(-1)).reshape(E, C)
        return flat_slot, valid

    flat_e = expert_ids.reshape(G, Tg * K)
    flat_slot, valid = jax.vmap(dispatch)(flat_e)            # (G, E, C)
    token_ids = flat_slot // K
    choice = flat_slot % K
    gates_ec = jax.vmap(lambda gv, t, c, v:
                        gv[t.reshape(-1), c.reshape(-1)].reshape(E, C) * v)(
        gate_vals, token_ids, choice, valid)                 # (G, E, C)

    x_e = jax.vmap(lambda xt, ti: jnp.take(xt, ti.reshape(-1), axis=0))(
        xg, token_ids).reshape(G, E, C, d)
    x_e = shard(x_e, "batch", "exp", None, None)
    h = jnp.einsum("gecd,edf->gecf", x_e, p["w1"].astype(dt))
    gg = jnp.einsum("gecd,edf->gecf", x_e, p["w3"].astype(dt))
    h = act_fn(cfg.act)(h) * gg
    h = shard(h, "batch", "exp", None, None)
    y_e = jnp.einsum("gecf,efd->gecd", h, p["w2"].astype(dt))
    y_e = (y_e * gates_ec[..., None].astype(dt))
    y_e = shard(y_e, "batch", "exp", None, None)

    # combine in bf16: the cross-"exp" reduction is the only collective
    seg = jnp.where(valid, token_ids, Tg)                    # (G, E, C)
    y = jax.vmap(lambda ye, sg: jax.ops.segment_sum(
        ye.reshape(E * C, d), sg.reshape(-1), num_segments=Tg + 1)[:Tg])(
        y_e, seg)
    y = shard(y.astype(dt), "batch", None, None)             # (G, Tg, d)

    dropped = 1.0 - valid.sum() / jnp.maximum(G * Tg * K, 1)
    return y, {"aux_loss": aux_loss, "drop_frac": dropped}
