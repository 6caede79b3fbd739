"""GQA attention: chunked (flash-style) prefill/train, ring-buffer
windowed KV caches, gemma-style logit softcaps, RoPE, QKV bias.

Layout notes
------------
q is kept grouped as (B, S, KH_eff, G, dh) where KH_eff = num_kv_heads *
cfg.kv_repeat (KV heads are replicated so KH_eff divides the TP degree —
the standard GQA-under-TP trick). Scores are computed grouped so the KV
cache is never materialized at full head count.

Sharding (logical names; resolved by the launcher's rules):
  train/prefill: "act_kv" -> model (head parallel), "act_kvseq" -> None
  decode:        "act_kv" -> None,  "act_kvseq" -> model (seq-parallel KV)

Latent attention (MLA, DeepSeek-V2/V3; ``cfg.kv_lora_rank > 0``) has
its own init/apply below: the cache holds the normed latent ``c_kv``
and one roped key ``k_pe`` per token. Prefill expands K/V through
``wkv_b``; decode uses the absorbed form, taking each head's query into
the latent space through ``wkv_b``'s key half and the attended latent
back out through its value half, so the cache is never expanded.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, rms_norm, softcap
from repro.sharding import shard

NEG_INF = -2.0e38
# ring slots per block of the latent decode's prefix (mla_apply)
DECODE_BLOCK = 512


# --------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------- #
def attn_init(key, cfg) -> Dict:
    if cfg.mla:
        return mla_init(key, cfg)
    d, H, KH, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(ks[0], (d, H, dh), dt, fan_in=d),
        "wk": dense_init(ks[1], (d, KH, dh), dt, fan_in=d),
        "wv": dense_init(ks[2], (d, KH, dh), dt, fan_in=d),
        "wo": dense_init(ks[3], (H, dh, d), dt, fan_in=H * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, dh), dt)
        p["bk"] = jnp.zeros((KH, dh), dt)
        p["bv"] = jnp.zeros((KH, dh), dt)
    return p


def attn_specs(cfg) -> Dict:
    if cfg.mla:
        return mla_specs(cfg)
    s = {
        "wq": ("embed", "heads", None),
        "wk": ("embed", "kv_heads", None),
        "wv": ("embed", "kv_heads", None),
        "wo": ("heads", None, "embed"),
    }
    if cfg.qkv_bias:
        s["bq"] = ("heads", None)
        s["bk"] = ("kv_heads", None)
        s["bv"] = ("kv_heads", None)
    return s


# --------------------------------------------------------------------- #
# core attend
# --------------------------------------------------------------------- #
def _masked_softmax(scores: jax.Array, mask: jax.Array) -> jax.Array:
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)  # rows that are fully masked stay finite
    e = jnp.exp(scores - m)
    e = jnp.where(mask, e, 0.0)
    return e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)


def _attend_block(q, k, v, q_pos, k_pos, *, window, cap, scale):
    """q: (B, Sq, KH, G, dh); k/v: (B, T, KH, dh); *_pos int32 (B, Sq)/(B, T)."""
    dt = q.dtype
    scores = jnp.einsum("bqkgd,btkd->bkgqt", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cap)
    scores = shard(scores, "batch", "act_kv", None, None, "act_kvseq")
    mask = (k_pos[:, None, :] <= q_pos[:, :, None]) & (k_pos[:, None, :] >= 0)
    if window is not None:
        win_ok = k_pos[:, None, :] > (q_pos[:, :, None] - window)
        mask = mask & jnp.where(window > 0, win_ok, True)
    w = _masked_softmax(scores, mask[:, None, None, :, :])
    out = jnp.einsum("bkgqt,btkd->bqkgd", w.astype(dt), v,
                     preferred_element_type=jnp.float32)
    return out.astype(dt)


def attend(q, k, v, q_pos, k_pos, *, window=None, cap=0.0, scale=1.0,
           q_chunk: int = 1024, unroll: bool = False):
    """Chunked attention over the query axis (memory ~ Sq_chunk * T)."""
    B, Sq = q.shape[0], q.shape[1]
    if Sq <= q_chunk or Sq % q_chunk != 0:
        return _attend_block(q, k, v, q_pos, k_pos,
                             window=window, cap=cap, scale=scale)
    nc = Sq // q_chunk
    qs = q.reshape(B, nc, q_chunk, *q.shape[2:]).swapaxes(0, 1)
    ps = q_pos.reshape(B, nc, q_chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(_, qc_pc):
        qc, pc = qc_pc
        return (), _attend_block(qc, k, v, pc, k_pos,
                                 window=window, cap=cap, scale=scale)

    _, out = jax.lax.scan(body, (), (qs, ps), unroll=unroll)
    # v's head size, which latent attention sets apart from q's
    return out.swapaxes(0, 1).reshape(B, Sq, *out.shape[3:])


# --------------------------------------------------------------------- #
# cache helpers (ring buffer when T < full sequence)
# --------------------------------------------------------------------- #
def _quant_kv(x):
    """Per-(position, head) symmetric int8 quantization of K/V rows --
    the paper's 8-bit ex-situ storage discipline applied to the decode
    cache (Perf cell C). Returns (codes int8, scale f32 without dh)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def _dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_attn_cache(cfg, batch: int, cache_len: int, dtype) -> Dict:
    if cfg.mla:
        return {"c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank),
                                  dtype),
                "k_pe": jnp.zeros((batch, cache_len,
                                   cfg.qk_rope_head_dim), dtype)}
    KH_eff = cfg.num_kv_heads * cfg.kv_repeat
    shp = (batch, cache_len, KH_eff, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        sshp = shp[:-1]
        return {"k": jnp.zeros(shp, jnp.int8),
                "v": jnp.zeros(shp, jnp.int8),
                "ks": jnp.zeros(sshp, jnp.float32),
                "vs": jnp.zeros(sshp, jnp.float32)}
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def attn_cache_specs(cfg) -> Dict:
    if cfg.mla:
        return {"c_kv": ("batch", "act_kvseq", None),
                "k_pe": ("batch", "act_kvseq", None)}
    s = {"k": ("batch", "act_kvseq", "act_kv", None),
         "v": ("batch", "act_kvseq", "act_kv", None)}
    if cfg.kv_cache_dtype == "int8":
        s["ks"] = ("batch", "act_kvseq", "act_kv")
        s["vs"] = ("batch", "act_kvseq", "act_kv")
    return s


def _ring_positions(pos: jax.Array, T: int) -> jax.Array:
    """Absolute position stored in each ring slot after writing `pos`."""
    j = jnp.arange(T, dtype=jnp.int32)
    return pos - ((pos % T - j) % T)


def _decode_prefix(pos_b, T: int):
    """The latent decode's prefixes of a ring of ``T`` slots: their
    lengths, whole blocks of ``DECODE_BLOCK`` slots from slot 0 (the
    last one cut at T), and the index of the shortest that holds every
    lane's written slots once each lane has written position ``pos_b``
    (B,): the whole ring once a lane has wrapped. numpy or jax."""
    block = min(DECODE_BLOCK, T)
    ends = [min(n, T) for n in range(block, T + block, block)]
    return ends, pos_b.max().clip(max=T - 1) // block


def _slot_write(leaf: jax.Array, layer, bidx: jax.Array, idx: jax.Array,
                val: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write ``val`` (B, ...) at ring slot ``idx`` (B,) of every lane
    → (new leaf, this layer's view). With ``layer`` set the leaf is the
    whole layer-stacked cache (L, B, T, ...) and is updated in place
    at that layer, so a donated cache is never copied."""
    if layer is None:
        new = leaf.at[bidx, idx].set(val.astype(leaf.dtype))
        return new, new
    new = leaf.at[layer, bidx, idx].set(val.astype(leaf.dtype))
    return new, new[layer]


def _store_prefill(cache_len: int, k: jax.Array) -> jax.Array:
    """Store a prefilled sequence (B, S, KH, dh) into a ring of length T."""
    S = k.shape[1]
    if S <= cache_len:
        pad = cache_len - S
        return jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    last = k[:, S - cache_len:, :, :]
    shift = (S - cache_len) % cache_len
    return jnp.roll(last, shift, axis=1)


def _store_prefill_scale(cache_len: int, s: jax.Array) -> jax.Array:
    """The (B, S, KH) scale companion of ``_store_prefill``."""
    S = s.shape[1]
    if S <= cache_len:
        return jnp.pad(s, ((0, 0), (0, cache_len - S), (0, 0)))
    last = s[:, S - cache_len:, :]
    shift = (S - cache_len) % cache_len
    return jnp.roll(last, shift, axis=1)


# --------------------------------------------------------------------- #
# full layer apply
# --------------------------------------------------------------------- #
def attn_apply(p: Dict, cfg, x: jax.Array, *, positions: jax.Array,
               mode: str, cache: Optional[Dict] = None,
               window=None, project=None, layer=None,
               stats=None) -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B, S, d). positions: (B, S) absolute token positions.

    mode: "train" (no cache), "prefill" (build cache), "decode" (S == 1,
    read+update cache; ``per_slot`` lets every batch lane hold its own
    position — the continuous-batching serving path).

    project: optional ``(name, x (B, S, d_in)) -> (B, S, d_out)``
    override for the four linear projections ("wq"/"wk"/"wv"/"wo");
    ``repro.lm`` routes them through crossbar-mapped tile grids while
    rope, softmax, and cache surgery below stay host-graph glue. QKV
    biases are still added here, so a projection backend must not fold
    them in.

    layer: per-slot decode only — ``cache`` is then the whole
    layer-stacked cache, written in place at this layer and returned
    whole (``repro.lm``'s unrolled decode).
    stats: see :func:`mla_apply` (latent attention only).
    Returns (out (B, S, d), new_cache)."""
    if cfg.mla:
        return mla_apply(p, cfg, x, positions=positions, mode=mode,
                         cache=cache, project=project, layer=layer,
                         stats=stats)
    dt = x.dtype
    B, S, _ = x.shape
    H, KH, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    KH_eff = KH * cfg.kv_repeat
    G = H // KH_eff
    scale = cfg.attn_scale if cfg.attn_scale else dh ** -0.5

    if project is None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    else:
        q = project("wq", x).reshape(B, S, H, dh)
        k = project("wk", x).reshape(B, S, KH, dh)
        v = project("wv", x).reshape(B, S, KH, dh)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)

    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.kv_repeat > 1:
        k = jnp.repeat(k, cfg.kv_repeat, axis=2)
        v = jnp.repeat(v, cfg.kv_repeat, axis=2)

    q = q.reshape(B, S, KH_eff, G, dh)
    q = shard(q, "batch", None, "act_kv", None, None)
    k = shard(k, "batch", "act_kvseq", "act_kv", None)
    v = shard(v, "batch", "act_kvseq", "act_kv", None)

    new_cache = None
    if mode == "decode":
        assert cache is not None and S == 1
        T = cache["k"].shape[1]
        quant = cfg.kv_cache_dtype == "int8"
        if quant:
            kq, ks_new = _quant_kv(k)
            vq, vs_new = _quant_kv(v)
        else:
            # explicit downcast into the cache dtype — jax scatter is
            # deprecating the implicit f32→bf16 cast (FutureWarning)
            kq = k.astype(cache["k"].dtype)
            vq = v.astype(cache["v"].dtype)
            ks_new, vs_new = None, None
        if cfg.decode_per_slot:
            # continuous batching: every slot decodes at its own position
            pos_b = positions[:, 0]                      # (B,)
            T = cache["k"].shape[-3]
            idx = pos_b % T
            bidx = jnp.arange(B)
            full = {}
            full["k"], ck = _slot_write(cache["k"], layer, bidx, idx,
                                        kq[:, 0])
            full["v"], cv = _slot_write(cache["v"], layer, bidx, idx,
                                        vq[:, 0])
            if quant:
                full["ks"], cks = _slot_write(cache["ks"], layer, bidx,
                                              idx, ks_new[:, 0])
                full["vs"], cvs = _slot_write(cache["vs"], layer, bidx,
                                              idx, vs_new[:, 0])
            k_pos = jax.vmap(_ring_positions, (0, None))(pos_b, T)
        else:
            assert layer is None, "layer-indexed decode is per-slot"
            pos = positions[0, 0]  # lockstep decode: scalar position
            idx = pos % T
            ck = jax.lax.dynamic_update_slice(cache["k"], kq, (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], vq, (0, idx, 0, 0))
            if quant:
                cks = jax.lax.dynamic_update_slice(cache["ks"], ks_new,
                                                   (0, idx, 0))
                cvs = jax.lax.dynamic_update_slice(cache["vs"], vs_new,
                                                   (0, idx, 0))
            k_pos = jnp.broadcast_to(_ring_positions(pos, T)[None, :],
                                     (B, T))
        ck = shard(ck, "batch", "act_kvseq", "act_kv", None)
        cv = shard(cv, "batch", "act_kvseq", "act_kv", None)
        if quant:
            new_cache = {"k": ck, "v": cv, "ks": cks, "vs": cvs}
            k_att = _dequant_kv(ck, cks, dt)
            v_att = _dequant_kv(cv, cvs, dt)
        else:
            new_cache = {"k": ck, "v": cv}
            k_att, v_att = ck, cv
        if layer is not None:
            new_cache = full
        out = _attend_block(q, k_att, v_att, positions, k_pos,
                            window=window, cap=cfg.attn_softcap, scale=scale)
    else:
        k_pos = positions
        out = attend(q, k, v, positions, k_pos,
                     window=window, cap=cfg.attn_softcap, scale=scale,
                     unroll=not cfg.scan_layers)
        if mode == "prefill":
            T = min(S, cfg.sliding_window) if window is not None and \
                isinstance(window, int) and window > 0 else S
            if cfg.kv_cache_dtype == "int8":
                kq, ks_new = _quant_kv(k)
                vq, vs_new = _quant_kv(v)
                new_cache = {"k": _store_prefill(T, kq),
                             "v": _store_prefill(T, vq),
                             "ks": _store_prefill_scale(T, ks_new),
                             "vs": _store_prefill_scale(T, vs_new)}
            else:
                new_cache = {"k": _store_prefill(T, k),
                             "v": _store_prefill(T, v)}

    out = out.reshape(B, S, H, dh)
    if project is None:
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    else:
        out = project("wo", out.reshape(B, S, H * dh))
    return out, new_cache


# --------------------------------------------------------------------- #
# multi-head latent attention (MLA)
# --------------------------------------------------------------------- #
# the per-head halves of ``wkv_b`` as linears of their own: the key
# half takes a head's q_nope into the latent (absorbed decode), the
# value half takes the attended latent back out
MLA_HEAD_LINEARS = ("wkv_b.k", "wkv_b.v")


def mla_init(key, cfg) -> Dict:
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": dense_init(ks[0], (d, H, dn + dr), dt, fan_in=d),
        "wkv_a": dense_init(ks[1], (d, r + dr), dt, fan_in=d),
        "kv_norm": jnp.ones((r,), dt),
        "wkv_b": dense_init(ks[2], (r, H, dn + dv), dt, fan_in=r),
        "wo": dense_init(ks[3], (H, dv, d), dt, fan_in=H * dv),
    }


def mla_specs(cfg) -> Dict:
    return {"wq": ("embed", "heads", None), "wkv_a": ("embed", None),
            "kv_norm": (None,), "wkv_b": (None, "heads", None),
            "wo": ("heads", None, "embed")}


def mla_linears(cfg, p: Dict) -> Dict[str, jax.Array]:
    """The layer's matmuls as (d_in, d_out) matrices, and the per-head
    ``wkv_b`` halves as (H, d_in, d_out) stacks (``MLA_HEAD_LINEARS``)."""
    dn = cfg.qk_nope_head_dim
    wkv_b = p["wkv_b"]                                   # (r, H, dn + dv)
    return {
        "wq": p["wq"].reshape(p["wq"].shape[0], -1),
        "wkv_a": p["wkv_a"],
        "wkv_b": wkv_b.reshape(wkv_b.shape[0], -1),
        "wo": p["wo"].reshape(-1, p["wo"].shape[-1]),
        "wkv_b.k": jnp.transpose(wkv_b[:, :, :dn], (1, 2, 0)),  # (H, dn, r)
        "wkv_b.v": jnp.transpose(wkv_b[:, :, dn:], (1, 0, 2)),  # (H, r, dv)
    }


def _mla_dense_projector(cfg, p: Dict):
    mats = mla_linears(cfg, p)

    def project(name: str, x: jax.Array) -> jax.Array:
        w = mats[name].astype(x.dtype)
        if name in MLA_HEAD_LINEARS:                     # x (N, H, d_in)
            return jnp.einsum("nhi,hio->nho", x, w)
        return jnp.einsum("...i,io->...o", x, w)
    return project


def mla_apply(p: Dict, cfg, x: jax.Array, *, positions: jax.Array,
              mode: str, cache: Optional[Dict] = None, project=None,
              layer=None, stats=None) -> Tuple[jax.Array, Optional[Dict]]:
    """DeepSeek-V3 attention with no q-LoRA. x: (B, S, d).

    q = x·wq split into q_nope (dn) and q_pe (dr, roped); x·wkv_a splits
    into the latent c (rank r, rms-normed by ``kv_norm``) and one roped
    key k_pe shared by every head. Scores are (q_nope·k_nope + q_pe·k_pe)
    · scale with scale 1/sqrt(dn + dr) unless ``cfg.attn_scale``.

    prefill/train: k_nope and v = c·wkv_b per head, plain causal
    attention; prefill returns the cache {c_kv: c, k_pe}.
    decode (S == 1): c and k_pe are written at each lane's ring slot,
    then q_nope·k_nope = (wkv_b_k q_nope)·c (absorbed) and the output
    is wkv_b_v·(Σ_t a_t c_t), read straight from the latent cache, over
    the ``_decode_prefix`` that holds every lane's written slots: those
    past every lane's newest are unwritten and weigh exactly 0. A list
    ``stats`` gets, per decode, int32 (2,): the slots each lane's
    attention read, from the branch the step took, and the ring's.

    ``project(name, x)`` overrides the matmuls (``mla_linears`` names;
    the per-head ``MLA_HEAD_LINEARS`` take and give (N, H, ·));
    ``layer`` as in :func:`attn_apply`."""
    dt = x.dtype
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = cfg.attn_scale if cfg.attn_scale else (dn + dr) ** -0.5
    if project is None:
        project = _mla_dense_projector(cfg, p)

    q = project("wq", x).reshape(B, S, H, dn + dr)
    q_nope = q[..., :dn]
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    kv = project("wkv_a", x)
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]

    if mode == "decode":
        assert cache is not None and S == 1
        pos_b = positions[:, 0]                          # (B,)
        T = cache["c_kv"].shape[-2]
        idx = pos_b % T
        bidx = jnp.arange(B)
        full_c, _ = _slot_write(cache["c_kv"], layer, bidx, idx, c[:, 0])
        full_pe, _ = _slot_write(cache["k_pe"], layer, bidx, idx,
                                 k_pe[:, 0])
        q_lat = project("wkv_b.k", q_nope.reshape(B, H, dn))    # (B, H, r)

        def attend_prefix(n):
            # the ring's first n slots, sliced from the written cache
            # itself (layer-stacked or not): no layer slab is copied
            def prefix(full):
                part = full[:, :n] if layer is None else full[layer, :, :n]
                return part.astype(dt)

            def branch(full_c, full_pe, q_lat, q_pe):
                c_all, pe_all = prefix(full_c), prefix(full_pe)
                scores = (jnp.einsum("bhr,btr->bht", q_lat, c_all,
                                     preferred_element_type=jnp.float32) +
                          jnp.einsum("bhe,bte->bht", q_pe, pe_all,
                                     preferred_element_type=jnp.float32)
                          ) * scale
                kp = jax.vmap(_ring_positions, (0, None))(pos_b, T)[:, :n]
                mask = (kp <= pos_b[:, None]) & (kp >= 0)
                w = _masked_softmax(scores, mask[:, None, :])
                return jnp.einsum("bht,btr->bhr", w.astype(dt), c_all,
                                  preferred_element_type=jnp.float32
                                  ), jnp.int32(n)
            return branch

        ends, i = _decode_prefix(pos_b, T)
        o_lat, read = jax.lax.switch(
            i, [attend_prefix(n) for n in ends],
            full_c, full_pe, q_lat, q_pe[:, 0])
        if stats is not None:
            stats.append(jnp.stack([read, jnp.int32(T)]))
        o_lat = o_lat.astype(dt)
        out = project("wkv_b.v", o_lat).reshape(B, S, H * dv)
        return project("wo", out), {"c_kv": full_c, "k_pe": full_pe}

    kvb = project("wkv_b", c).reshape(B, S, H, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe[:, :, None, :],
                                         (B, S, H, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_pe], axis=-1)[:, :, :, None, :]
    out = attend(qf, k, kvb[..., dn:], positions, positions, scale=scale,
                 unroll=not cfg.scan_layers)             # (B, S, H, 1, dv)
    out = project("wo", out.reshape(B, S, H * dv))
    new_cache = {"c_kv": c, "k_pe": k_pe} if mode == "prefill" else None
    return out, new_cache
