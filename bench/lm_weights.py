"""Weights of an LM configuration, made from the seed on the device.

No trained weights are loaded. The parameter tree is drawn here, on the
benchmark's side, in the layout that ``repro.lm.compile_lm`` takes
(``AppSpec(params=...)``) and that ``bench/lm_reference.py`` reads, so
the program's own initialisation takes no part in what is compared:

  embedding          normal(0, 1)
  every linear       normal(0, 1/fan_in)
  norm scales        uniform(0.8, 1.2): a path that drops or swaps a
                     norm reads other numbers than the reference
  router bias        normal(0, ROUTER_BIAS_STD): the config does not
                     give ``e_score_correction_bias``; it is small and
                     nonzero, so a path that ignores it chooses other
                     experts

Tree (``k`` dense layers, ``n`` MoE layers, each group stacked on a
leading layer axis; ``Eh`` held experts of the router's ``E``):

  embed.table (V, d); lm_head.w (d, V); final_norm (d,)
  stack.{dense,moe}: attn_norm, mlp_norm (d,);
    attn: wq (d, H, dn + dr) [q_nope | q_pe], wkv_a (d, r + dr)
          [c | k_pe], kv_norm (r,), wkv_b (r, H, dn + dv) [k_nope | v],
          wo (H, dv, d)
    dense mlp: w1, w3 (d, F), w2 (F, d)
    moe mlp: router (d, E), router_bias (E,), w1, w3 (Eh, d, f),
             w2 (Eh, f, d), shared.w1, shared.w3 (d, s f),
             shared.w2 (s f, d)
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from bench import traffic

ROUTER_BIAS_STD = 0.01
NORM_RANGE = (0.8, 1.2)


@functools.partial(jax.jit, static_argnums=1)
def _make(key, dims: Tuple[int, ...]) -> dict:
    (V, d, H, dn, dr, dv, r, F, f, E, Eh, s, k, n) = dims
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std=1.0):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def linear(shape, fan_in):
        return normal(shape, fan_in ** -0.5)

    def norm(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  *NORM_RANGE)

    def blocks(L, moe):
        p = {"attn": {"wq": linear((L, d, H, dn + dr), d),
                      "wkv_a": linear((L, d, r + dr), d),
                      "kv_norm": norm((L, r)),
                      "wkv_b": linear((L, r, H, dn + dv), r),
                      "wo": linear((L, H, dv, d), H * dv)},
             "attn_norm": norm((L, d)), "mlp_norm": norm((L, d))}
        if moe:
            p["mlp"] = {"router": linear((L, d, E), d),
                        "router_bias": normal((L, E), ROUTER_BIAS_STD),
                        "w1": linear((L, Eh, d, f), d),
                        "w3": linear((L, Eh, d, f), d),
                        "w2": linear((L, Eh, f, d), f),
                        "shared": {"w1": linear((L, d, s * f), d),
                                   "w3": linear((L, d, s * f), d),
                                   "w2": linear((L, s * f, d), s * f)}}
        else:
            p["mlp"] = {"w1": linear((L, d, F), d),
                        "w3": linear((L, d, F), d),
                        "w2": linear((L, F, d), F)}
        return p

    return {"embed": {"table": normal((V, d))},
            "stack": {"dense": blocks(k, False), "moe": blocks(n, True)},
            "final_norm": norm((d,)),
            "lm_head": {"w": linear((d, V), d)}}


def dims_of(cfg: dict) -> Tuple[int, ...]:
    k = int(cfg["first_k_dense_replace"])
    if k < 1:
        raise ValueError("the LM weights lay out a dense prefix: "
                         "first_k_dense_replace must be >= 1")
    return (cfg["vocab_size"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["kv_lora_rank"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["published"]["n_routed_experts"], cfg["n_routed_experts"],
            cfg["n_shared_experts"], k, cfg["num_hidden_layers"] - k)


def make(cfg: dict, seed: int) -> dict:
    """The configuration's weights for ``seed``: one jitted call,
    float32 on the default device."""
    key = jax.random.PRNGKey(int(traffic.rng(seed, 0).integers(0, 2**31)))
    return _make(key, tuple(int(x) for x in dims_of(cfg)))
