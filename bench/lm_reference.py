"""The plain reference of the LM cells: DeepSeek-V3 blocks (latent
attention, a dense layer, sigmoid ``noaux_tc`` MoE layers with shared
experts) in straight ``jax.numpy``, float32, one sequence at a time,
with no cache, no tiles and no batching.

It follows the published equations (DeepSeek-V3 report, HF
``modeling_deepseek``), reads the weights the benchmark made and
imports nothing of the program. For a sequence of length L:

  attention  q = x Wq = [q_nope | q_pe];  x Wkv_a = [c | k_pe];
             c = rmsnorm(c) * kv_norm;  q_pe, k_pe roped (the pairs
             (i, i + dr/2), as the program lays them out);
             [k_nope | v] = c Wkv_b per head;
             a = softmax((q_nope.k_nope + q_pe.k_pe) / sqrt(dn + dr));
             out = (a v) Wo
  MoE        s = sigmoid(x Wr); the top-k of s + bias are chosen; their
             s, over their sum, times ``routed_scaling_factor``, weight
             the held experts' SwiGLU outputs; plus the shared experts

Only the held experts compute, as on the chip whose share the cell
stands for, and the vocabulary is the configuration's slice.

The configuration states the latent cache's precision
(``latent_cache_dtype``). With a bfloat16 cache, what a decoding query
reads of an earlier position is its ``c`` and ``k_pe`` rounded to
bfloat16, while the prompt's own queries (one prefill) read them
unrounded: queries at positions >= ``prompt_len`` attend over the
rounded latents. Queries go in blocks of ``Q_BLOCK`` so that the scores
fit.

``forward`` also marks the positions whose routing is near a tie: the
6th and 7th largest selection scores (``s + bias``) of some MoE layer
lie within ``margin`` and one of the two is a held expert, so rounding
may choose differently there. A different choice at one position
reaches every later position of its sequence through attention, so a
checked position is left out when any position up to it is near a tie.

``mode`` sets the matmul precision: ``highest`` (the reference),
``high`` (the backend's HIGH: three bfloat16 passes on a TPU, exact on
the CPU) or ``bf16`` (operands rounded to bfloat16, float32
accumulation, on any backend): the controls one step lower.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _mm(spec: str, a, b, mode: str):
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH}[mode]
    return jnp.einsum(spec, a, b, precision=prec,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (L, ..., dr) rotated by position; pairs (i, i + dr/2)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _cached(x, dtype: str):
    """What the latent cache gives back of ``x``."""
    return x.astype(dtype).astype(jnp.float32)


def _attention(p, x, pos, prompt_len, c, mode):
    """One layer's latent attention over the whole sequence."""
    H, dn, dr, dv, r, eps, theta, cache_dtype = c
    L = x.shape[0]
    q = _mm("ld,dhk->lhk", x, p["wq"], mode)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, theta)
    kv = _mm("ld,dk->lk", x, p["wkv_a"], mode)
    lat = _rms(kv[:, :r], p["kv_norm"], eps)
    k_pe = _rope(kv[:, r:], pos, theta)

    def keys(lat, k_pe):
        kvb = _mm("lr,rhk->lhk", lat, p["wkv_b"], mode)
        k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
            k_pe[:, None, :], (L, H, dr))], -1)
        return k, kvb[..., dn:]

    k_f, v_f = keys(lat, k_pe)                  # what the prefill reads
    k_c, v_c = keys(_cached(lat, cache_dtype),  # what the cache holds
                    _cached(k_pe, cache_dtype))
    qf = jnp.concatenate([q_nope, q_pe], -1)
    scale = (dn + dr) ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qf, i * Q_BLOCK, Q_BLOCK)
        qpos = jax.lax.dynamic_slice_in_dim(pos, i * Q_BLOCK, Q_BLOCK)
        decode = (qpos >= prompt_len)[:, None, None]
        s_f = _mm("qhk,thk->qht", qb, k_f, mode)
        s_c = _mm("qhk,thk->qht", qb, k_c, mode)
        s = jnp.where(decode, s_c, s_f) * scale
        mask = (pos[None, :] <= qpos[:, None])[:, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o_f = _mm("qht,thv->qhv", a, v_f, mode)
        o_c = _mm("qht,thv->qhv", a, v_c, mode)
        return jnp.where(decode, o_c, o_f)

    out = jax.lax.map(block, jnp.arange(L // Q_BLOCK))
    out = out.reshape(L, H * dv)
    return _mm("lk,kd->ld", out, p["wo"].reshape(H * dv, -1), mode)


def _swiglu(x, w1, w3, w2, mode):
    h = jax.nn.silu(_mm("ld,df->lf", x, w1, mode)) * \
        _mm("ld,df->lf", x, w3, mode)
    return _mm("lf,fd->ld", h, w2, mode)


def _moe(p, x, held, c, mode, margin):
    top_k, scale = c
    s = jax.nn.sigmoid(_mm("ld,de->le", x, p["router"], mode))
    sel = s + p["router_bias"]
    _, ids = jax.lax.top_k(sel, top_k + 1)
    chosen = ids[:, :top_k]
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    y = _swiglu(x, p["shared"]["w1"], p["shared"]["w3"],
                p["shared"]["w2"], mode)
    for j, e in enumerate(held):
        gate = jnp.where(chosen == e, w, 0.0).sum(-1)
        y = y + gate[:, None] * _swiglu(x, p["w1"][j], p["w3"][j],
                                        p["w2"][j], mode)
    gap = jnp.take_along_axis(sel, ids[:, top_k - 1:top_k + 1], -1)
    edge = ids[:, top_k - 1:top_k + 1]
    held_edge = jnp.isin(edge, jnp.asarray(held)).any(-1)
    near = ((gap[:, 0] - gap[:, 1]) <= margin) & held_edge
    return y, near


@functools.partial(jax.jit, static_argnames=("shape", "mode", "held"))
def _forward(params, tokens, prompt_len, margin, *, shape: Tuple,
             mode: str, held: Tuple[int, ...]):
    (n_dense, H, dn, dr, dv, r, eps, theta, top_k, scale,
     cache_dtype) = shape
    L = tokens.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    stack = params["stack"]
    layers = [(jax.tree.map(lambda a, i=i: a[i], stack["dense"]), False)
              for i in range(n_dense)]
    n_moe = stack["moe"]["attn_norm"].shape[0]
    layers += [(jax.tree.map(lambda a, i=i: a[i], stack["moe"]), True)
               for i in range(n_moe)]
    near = jnp.zeros((L,), bool)
    for p, moe in layers:
        h = h + _attention(p["attn"], _rms(h, p["attn_norm"], eps), pos,
                           prompt_len,
                           (H, dn, dr, dv, r, eps, theta, cache_dtype),
                           mode)
        x = _rms(h, p["mlp_norm"], eps)
        if moe:
            y, n = _moe(p["mlp"], x, held, (top_k, scale), mode, margin)
            near = near | n
        else:
            y = _swiglu(x, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"],
                        mode)
        h = h + y
    h = _rms(h, params["final_norm"], eps)
    logits = _mm("ld,dv->lv", h, params["lm_head"]["w"], mode)
    return logits, jnp.cumsum(near) > 0


def forward(cfg: dict, params, tokens: np.ndarray, prompt_len: int,
            mode: str = "highest") -> Tuple[np.ndarray, np.ndarray]:
    """(logits (L, vocab), near (L,)) of one sequence: ``near[i]`` says
    some position <= i is near a routing tie. Every sequence is padded
    to the cache's length, rounded up to ``Q_BLOCK`` (causal: padding
    changes nothing before it), so all share one compile."""
    L = len(tokens)
    Lp = -(-max(L, int(cfg["cache_len"])) // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((Lp,), np.int32)
    toks[:L] = tokens
    shape = (int(cfg["first_k_dense_replace"]),
             int(cfg["num_attention_heads"]),
             int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
             int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]),
             float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
             int(cfg["num_experts_per_tok"]),
             float(cfg["routed_scaling_factor"]),
             str(cfg["latent_cache_dtype"]))
    with jax.default_matmul_precision(
            "highest" if mode == "highest" else "default"):
        logits, near = _forward(
            params, jnp.asarray(toks), jnp.int32(prompt_len),
            jnp.float32(cfg["check"]["near_margin"]), shape=shape,
            mode=mode, held=tuple(int(e) for e in cfg["held_experts"]))
    return np.asarray(logits)[:L], np.asarray(near)[:L]
