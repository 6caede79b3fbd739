"""Per-family block stacks, scanned over layers.

Every family exposes the same five functions so ``model.py`` stays
generic:

  init(key, cfg)                 -> stacked params
  specs(cfg)                     -> logical-axis spec tree (same structure)
  apply(p, cfg, h, positions, mode, cache) -> (h, new_cache, aux)
  init_cache(cfg, batch, cache_len, dtype) -> cache tree
  cache_specs(cfg)               -> logical-axis spec tree for the cache

Parameters are stacked along a leading scan axis (jax.vmap over per-layer
init); ``jax.lax.scan`` walks the stack so the HLO stays small regardless
of depth — essential for 40-80 layer models compiled on one CPU core.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import mlp_apply, mlp_init, mlp_specs, rms_norm
from repro.sharding import shard


def stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _remat(fn, cfg, mode):
    if mode != "train" or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol, prevent_cse=False)
    return jax.checkpoint(fn, prevent_cse=False)


def _zeros_like_aux(aux):
    return jax.tree.map(lambda x: jnp.zeros((), jnp.float32), aux)


def scan_stack(step, cfg, mode, h, stacked_params, cache, extras, aux0):
    """step(p_i, h, cache_i, extras_i) -> (h, cache_i', aux_i).

    ``cache``/``extras`` may be None. aux accumulates by summation."""
    body_core = step

    def body(carry, xs):
        h, aux_acc = carry
        p_i, c_i, e_i = xs
        h, c_new, aux = body_core(p_i, h, c_i, e_i)
        if aux:
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
        return (h, aux_acc), c_new

    body = _remat(body, cfg, mode)
    xs = (stacked_params, cache, extras)
    (h, aux), new_cache = jax.lax.scan(body, (h, aux0), xs,
                                       unroll=not cfg.scan_layers)
    return h, new_cache, aux


# ===================================================================== #
# dense / vlm / audio / moe transformer stacks
# ===================================================================== #
def _block_init(key, cfg, use_moe: bool):
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "attn": attn.attn_init(k1, cfg),
        "attn_norm": jnp.ones((d,), dt),
        "mlp_norm": jnp.ones((d,), dt),
    }
    p["mlp"] = moe_mod.moe_init(k2, cfg) if use_moe else \
        mlp_init(k2, d, cfg.dense_d_ff or cfg.d_ff, dt)
    if cfg.post_block_norm:
        p["attn_post"] = jnp.ones((d,), dt)
        p["mlp_post"] = jnp.ones((d,), dt)
    return p


def _block_specs(cfg, use_moe: bool):
    s = {
        "attn": attn.attn_specs(cfg),
        "attn_norm": (None,),
        "mlp_norm": (None,),
        "mlp": moe_mod.moe_specs(cfg) if use_moe else mlp_specs(),
    }
    if cfg.post_block_norm:
        s["attn_post"] = (None,)
        s["mlp_post"] = (None,)
    return s


def _block_apply(p, cfg, h, *, positions, mode, cache, window, use_moe,
                 project=None, mlp_fn=None, layer=None, attn_stats=None):
    """project/mlp_fn: optional overrides of the attention's linear
    projections (see ``attn.attn_apply``) and of the whole MLP or MoE
    layer; ``repro.lm`` substitutes crossbar-mapped tile grids for the
    block's matmuls while norms, residuals, rope, softmax, routing and
    cache surgery stay in this host graph. ``layer``: see
    ``attn.attn_apply``; ``attn_stats``: its ``stats``."""
    a_in = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    a_out, new_cache = attn.attn_apply(p["attn"], cfg, a_in,
                                       positions=positions, mode=mode,
                                       cache=cache, window=window,
                                       project=project, layer=layer,
                                       stats=attn_stats)
    if cfg.post_block_norm:
        a_out = rms_norm(a_out, p["attn_post"], cfg.norm_eps)
    h = h + a_out
    h = shard(h, "batch", "seq", None)

    m_in = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
    aux = {}
    if mlp_fn is not None:
        m_out = mlp_fn(p["mlp"], m_in)
    elif use_moe:
        m_out, aux = moe_mod.moe_apply(p["mlp"], cfg, m_in)
    else:
        m_out = mlp_apply(p["mlp"], m_in, cfg.act, m_in.dtype)
    if cfg.post_block_norm:
        m_out = rms_norm(m_out, p["mlp_post"], cfg.norm_eps)
    h = h + m_out
    h = shard(h, "batch", "seq", None)
    return h, new_cache, aux


def _layer_windows(cfg) -> jnp.ndarray:
    """Per-layer sliding window (0 = full). gemma2: even layers local."""
    if cfg.local_global:
        w = [cfg.sliding_window if i % 2 == 0 else 0
             for i in range(cfg.num_layers)]
    elif cfg.sliding_window and cfg.family not in ("hybrid",):
        w = [cfg.sliding_window] * cfg.num_layers
    else:
        w = [0] * cfg.num_layers
    return jnp.asarray(w, jnp.int32)


class DenseStack:
    use_moe = False

    @classmethod
    def init(cls, key, cfg):
        return stack_init(lambda k: _block_init(k, cfg, cls.use_moe), key,
                          cfg.num_layers)

    @classmethod
    def specs(cls, cfg):
        return _block_specs(cfg, cls.use_moe)

    @classmethod
    def apply(cls, p, cfg, h, *, positions, mode, cache=None):
        windows = _layer_windows(cfg)
        aux0 = {"aux_loss": jnp.zeros((), jnp.float32),
                "drop_frac": jnp.zeros((), jnp.float32)} if cls.use_moe else {}

        def step(p_i, h, c_i, w_i):
            return _block_apply(p_i, cfg, h, positions=positions, mode=mode,
                                cache=c_i, window=w_i, use_moe=cls.use_moe)

        return scan_stack(step, cfg, mode, h, p, cache, windows, aux0)

    @classmethod
    def init_cache(cls, cfg, batch, cache_len, dtype):
        one = attn.init_attn_cache(cfg, batch, cache_len, dtype)
        return jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), one)

    @classmethod
    def cache_specs(cls, cfg):
        cs = attn.attn_cache_specs(cfg)
        return jax.tree.map(lambda names: (None,) + names, cs,
                            is_leaf=lambda x: isinstance(x, tuple))


class MoEStack(DenseStack):
    """MoE blocks; with ``cfg.first_dense_layers`` = k the first k
    blocks are dense (width ``cfg.dense_d_ff``) and the parameters are
    {"dense": k stacked blocks, "moe": the rest}. The cache stays one
    stack over all layers."""
    use_moe = True

    @classmethod
    def init(cls, key, cfg):
        k = cfg.first_dense_layers
        if not k:
            return super().init(key, cfg)
        kd, km = jax.random.split(key)
        return {"dense": stack_init(lambda kk: _block_init(kk, cfg, False),
                                    kd, k),
                "moe": stack_init(lambda kk: _block_init(kk, cfg, True),
                                  km, cfg.num_layers - k)}

    @classmethod
    def specs(cls, cfg):
        if not cfg.first_dense_layers:
            return super().specs(cfg)
        return {"dense": _block_specs(cfg, False),
                "moe": _block_specs(cfg, True)}

    @classmethod
    def apply(cls, p, cfg, h, *, positions, mode, cache=None):
        k = cfg.first_dense_layers
        if not k:
            return super().apply(p, cfg, h, positions=positions,
                                 mode=mode, cache=cache)
        windows = _layer_windows(cfg)
        aux0 = {"aux_loss": jnp.zeros((), jnp.float32),
                "drop_frac": jnp.zeros((), jnp.float32)}

        def part(use_moe, params, c, w, h):
            def step(p_i, h, c_i, w_i):
                return _block_apply(p_i, cfg, h, positions=positions,
                                    mode=mode, cache=c_i, window=w_i,
                                    use_moe=use_moe)
            return scan_stack(step, cfg, mode, h, params, c, w, aux0)

        cut = (lambda lo, hi: None) if cache is None else \
            (lambda lo, hi: jax.tree.map(lambda x: x[lo:hi], cache))
        h, c_dense, _ = part(False, p["dense"], cut(0, k), windows[:k], h)
        h, c_moe, aux = part(True, p["moe"], cut(k, None), windows[k:], h)
        new_cache = c_moe if c_moe is None else jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), c_dense, c_moe)
        return h, new_cache, aux


# ===================================================================== #
# zamba-style hybrid: groups of (2 x Mamba2) + shared attention block
# ===================================================================== #
class HybridStack:
    """cfg.num_layers Mamba2 blocks; after every ``shared_attn_every`` of
    them one application of a single *shared* transformer block."""

    @staticmethod
    def _group_geometry(cfg):
        per = cfg.shared_attn_every
        assert cfg.num_layers % per == 0, "layers must tile into groups"
        return cfg.num_layers // per, per

    @classmethod
    def init(cls, key, cfg):
        G, per = cls._group_geometry(cfg)
        k1, k2, k3 = jax.random.split(key, 3)
        d = cfg.d_model
        dt = jnp.dtype(cfg.param_dtype)

        def group_init(k):
            ks = jax.random.split(k, per)
            return {
                "mamba": jax.vmap(
                    lambda kk: ssm_mod.mamba_init(kk, cfg))(ks),
                "mamba_norm": jnp.ones((per, d), dt),
            }

        return {
            "groups": stack_init(group_init, k1, G),
            "shared": _block_init(k2, cfg, use_moe=False),
        }

    @classmethod
    def specs(cls, cfg):
        mspec = jax.tree.map(lambda names: (None,) + names,
                             ssm_mod.mamba_specs(cfg),
                             is_leaf=lambda x: isinstance(x, tuple))
        return {
            "groups": {"mamba": mspec, "mamba_norm": (None, None)},
            "shared": _block_specs(cfg, use_moe=False),
        }

    @classmethod
    def apply(cls, p, cfg, h, *, positions, mode, cache=None):
        G, per = cls._group_geometry(cfg)
        shared = p["shared"]
        window = cfg.sliding_window if cfg.sliding_window else None

        def step(p_g, h, c_g, _):
            def inner(carry, xs):
                h = carry
                pm, norm_i, cm = xs
                m_in = rms_norm(h, norm_i, cfg.norm_eps)
                out, cm_new = ssm_mod.mamba_apply(pm, cfg, m_in, mode=mode,
                                                  cache=cm)
                if cm_new is None:  # train mode
                    cm_new = jnp.zeros((), jnp.int32)
                return h + out, cm_new

            xs = (p_g["mamba"], p_g["mamba_norm"],
                  c_g["mamba"] if c_g is not None else
                  jnp.zeros((per,), jnp.int32))
            h, cm_new = jax.lax.scan(inner, h, xs,
                                     unroll=not cfg.scan_layers)
            h = shard(h, "batch", "seq", None)
            h, ca_new, _ = _block_apply(shared, cfg, h, positions=positions,
                                        mode=mode,
                                        cache=None if c_g is None
                                        else c_g["attn"],
                                        window=window, use_moe=False)
            if mode == "train":
                return h, jnp.zeros((), jnp.int32), {}
            return h, {"mamba": cm_new, "attn": ca_new}, {}

        return scan_stack(step, cfg, mode, h, p["groups"], cache, None, {})

    @classmethod
    def init_cache(cls, cfg, batch, cache_len, dtype):
        G, per = cls._group_geometry(cfg)
        attn_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window \
            else cache_len
        mc = ssm_mod.init_mamba_cache(cfg, batch, dtype)
        ac = attn.init_attn_cache(cfg, batch, attn_len, dtype)
        stack = lambda t, n: jax.tree.map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), t)
        return stack({"mamba": stack(mc, per), "attn": ac}, G)

    @classmethod
    def cache_specs(cls, cfg):
        lead2 = lambda t: jax.tree.map(lambda n: (None, None) + n, t,
                                       is_leaf=lambda x: isinstance(x, tuple))
        lead1 = lambda t: jax.tree.map(lambda n: (None,) + n, t,
                                       is_leaf=lambda x: isinstance(x, tuple))
        return {"mamba": lead2(ssm_mod.mamba_cache_specs(cfg)),
                "attn": lead1(attn.attn_cache_specs(cfg))}


# ===================================================================== #
# xLSTM stack: groups of (period-1 mLSTM blocks + 1 sLSTM block)
# ===================================================================== #
class XLSTMStack:
    @staticmethod
    def _group_geometry(cfg):
        per = cfg.slstm_period
        assert cfg.num_layers % per == 0
        return cfg.num_layers // per, per - 1

    @classmethod
    def init(cls, key, cfg):
        G, n_m = cls._group_geometry(cfg)

        def group_init(k):
            k1, k2 = jax.random.split(k)
            return {
                "mlstm": stack_init(lambda kk: xlstm_mod.mlstm_init(kk, cfg),
                                    k1, n_m),
                "slstm": xlstm_mod.slstm_init(k2, cfg),
            }

        return {"groups": stack_init(group_init, key, G)}

    @classmethod
    def specs(cls, cfg):
        lead = lambda t: jax.tree.map(lambda n: (None,) + n, t,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return {"groups": {"mlstm": lead(xlstm_mod.mlstm_specs(cfg)),
                           "slstm": xlstm_mod.slstm_specs(cfg)}}

    @classmethod
    def apply(cls, p, cfg, h, *, positions, mode, cache=None):
        G, n_m = cls._group_geometry(cfg)

        def step(p_g, h, c_g, _):
            def inner(carry, xs):
                h = carry
                pm, cm = xs
                out, cm_new = xlstm_mod.mlstm_apply(pm, cfg, h, mode=mode,
                                                    cache=cm)
                if cm_new is None:  # train mode
                    cm_new = jnp.zeros((), jnp.int32)
                return h + out, cm_new

            xs = (p_g["mlstm"],
                  c_g["mlstm"] if c_g is not None
                  else jnp.zeros((n_m,), jnp.int32))
            h, cm_new = jax.lax.scan(inner, h, xs,
                                     unroll=not cfg.scan_layers)
            h, cs_new = xlstm_mod.slstm_apply(p_g["slstm"], cfg, h, mode=mode,
                                              cache=None if c_g is None
                                              else c_g["slstm"])
            h = shard(h, "batch", "seq", None)
            if mode == "train":
                return h, jnp.zeros((), jnp.int32), {}
            return h, {"mlstm": cm_new, "slstm": cs_new}, {}

        return scan_stack(step, cfg, mode, h, p["groups"], cache, None, {})

    @classmethod
    def init_cache(cls, cfg, batch, cache_len, dtype):
        G, n_m = cls._group_geometry(cfg)
        mc = xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
        sc = xlstm_mod.init_slstm_cache(cfg, batch)
        stack = lambda t, n: jax.tree.map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), t)
        return stack({"mlstm": stack(mc, n_m), "slstm": sc}, G)

    @classmethod
    def cache_specs(cls, cfg):
        lead2 = lambda t: jax.tree.map(lambda n: (None, None) + n, t,
                                       is_leaf=lambda x: isinstance(x, tuple))
        lead1 = lambda t: jax.tree.map(lambda n: (None,) + n, t,
                                       is_leaf=lambda x: isinstance(x, tuple))
        return {"mlstm": lead2(xlstm_mod.mlstm_cache_specs(cfg)),
                "slstm": lead1(xlstm_mod.slstm_cache_specs(cfg))}


def get_stack(cfg):
    if cfg.family in ("dense", "vlm", "audio"):
        return DenseStack
    if cfg.family == "moe":
        return MoEStack
    if cfg.family == "hybrid":
        return HybridStack
    if cfg.family == "ssm":
        return XLSTMStack
    raise ValueError(f"unknown family {cfg.family!r}")
