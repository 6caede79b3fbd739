"""The LM tenant: a DeepSeek-V3-style MoE model (latent attention, a
dense layer, sigmoid ``noaux_tc`` expert layers) mapped onto crossbar
tiles by ``repro.lm.compile_lm`` and served through
``repro.deploy.deploy`` as one chip of an expert-parallel group.

The configuration file holds the model's published ``config.json``
keys (with the cut ones in ``reduced`` and their published values in
``published``), the experts this chip holds, the fabric, the lanes and
the cache. The functions are those ``tenants/sensor.py`` lists:

  make_params   the whole parameter tree from the seed, drawn on the
                benchmark's side (``bench/lm_weights.py``) in one jitted
                call on the device (the held experts only, the
                vocabulary slice only)
  deploy        ``deploy()`` of one LM app: ``compile_lm`` programs
                every linear, ``LMMember`` serves the latent cache
  make_load     the requests: each prompt's length drawn from the mix's
                lengths by the seed, ids uniform over the vocabulary
                slice, ``max_new_tokens`` = cache - prompt; and the
                seeded (lane, step) pairs whose logits are checked
  warm          serves a one-token request of each prompt length the
                load holds, then submits every request and steps until every
                lane has been admitted (prefill) and has decoded: every
                shape the window runs is compiled, and the window is
                decode only
  window        decode steps through ``Deployment.step`` for the
                window's seconds; a step is a call, ``finished`` when
                it emitted a token on every live lane; ``items`` are
                tokens; the checked pairs' logits are kept
  check         the plain reference (``bench/lm_reference.py``)
                teacher-forced on prompt + emitted tokens, logits
                compared: ``rel_err`` and ``rms_err`` relative to the
                largest reference logit, positions near a routing tie
                left out and counted; ``expert_dropped`` of the window
  flops         weight FLOPs per token of this chip's share
  min_call_seconds
                the least time of one decode step: weight and head
                bytes at 4 B, the latent cache at the lanes' positions,
                or the FLOPs at the peak, whichever is larger
  rehearsal     a few-layer, 64-wide preset of the same architecture
                (``cfg["rehearsal"]``) for the CPU
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_reference, lm_weights, loops, reference, traffic

APP = "lm"
# the published routing this tenant implements; anything else is
# refused rather than run as something it is not
ROUTING = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
           "q_lora_rank": None, "hidden_act": "silu",
           "attention_bias": False, "tie_word_embeddings": False,
           "norm_topk_prob": True}
WEIGHT_BYTES = 4            # float32 programmed weights, as workcount


@dataclasses.dataclass(frozen=True)
class Load:
    """The requests of one run, in submission order, and the checked
    (lane, step) pairs of its window."""
    prompts: Tuple[np.ndarray, ...]
    max_new: Tuple[int, ...]
    check_lanes: Tuple[int, ...]
    check_steps: frozenset


@dataclasses.dataclass
class LMWindow(loops.Window):
    """A decode window: besides ``loops.Window``, the lanes' positions
    at its start and the MoE dispatch counts (assignments, rows,
    dropped) of its steps."""
    positions: Optional[np.ndarray] = None
    expert: Optional[np.ndarray] = None


def model_config(cfg: dict):
    """The ``repro`` ModelConfig of the configuration file."""
    from repro.configs import ModelConfig

    for key, want in ROUTING.items():
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r}: the LM tenant "
                             f"implements {want!r}")
    held = tuple(int(e) for e in cfg["held_experts"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError("held_experts must list n_routed_experts ids")
    return ModelConfig(
        name=cfg.get("name", "lm"), family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        num_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        router_score="sigmoid", router_bias=True,
        router_scale=float(cfg["routed_scaling_factor"]),
        held_experts=held,
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], compute_dtype="float32",
        kv_cache_dtype=cfg["latent_cache_dtype"])


def make_params(cfg: dict, seed: int):
    model_config(cfg)           # refuse a configuration before its weights
    return lm_weights.make(cfg, seed)


def deploy(cfg: dict, params, chips: int):
    from repro.deploy import AppSpec, DeploymentSpec, deploy as deploy_app

    app = AppSpec(APP, model_config(cfg), params=params,
                  system=cfg["system"],
                  geom=(cfg["core"]["rows"], cfg["core"]["cols"]),
                  lanes_per_chip=cfg["lanes_per_chip"],
                  cache_len=cfg["cache_len"])
    return deploy_app(DeploymentSpec(apps=(app,), n_chips=chips,
                                     use_kernel=cfg["use_kernel"]))


def make_load(cfg: dict, mix: dict, seed: int, seconds: float) -> Load:
    n, lanes = int(mix["requests"]), int(cfg["lanes_per_chip"])
    lengths = traffic.rng(seed, 1).choice(
        np.asarray(mix["prompt_lengths"], np.int64), n)
    gen = traffic.rng(seed, 2)
    prompts = tuple(gen.integers(0, cfg["vocab_size"], int(k),
                                 dtype=np.int64) for k in lengths)
    pick = traffic.rng(seed, 4)
    checked = sorted(int(x) for x in pick.choice(
        lanes, int(mix["checked_lanes"]), replace=False))
    steps = pick.choice(int(mix["checked_step_range"]),
                        int(mix["checked_steps"]), replace=False)
    return Load(prompts=prompts,
                max_new=tuple(int(cfg["cache_len"] - k) for k in lengths),
                check_lanes=tuple(checked),
                check_steps=frozenset(int(s) for s in steps))


def _member(dep):
    return dep.router.members[APP]


def warm(dep, cfg: dict, load: Load) -> None:
    member = _member(dep)
    # a one-token request of each prompt length, served to its end:
    # every prefill shape and the slot clear are compiled, whichever
    # lengths the first admissions draw
    for k in sorted({len(p) for p in load.prompts}):
        dep.submit_tokens(APP, np.zeros(k, np.int64), 1)
    while dep.router.active or dep.router.queue:
        dep.step()
    for prompt, new in zip(load.prompts, load.max_new):
        dep.submit_tokens(APP, prompt, new)
    lanes = member.lanes
    for _ in range(2):
        while len(dep.router.active) < min(lanes, len(load.prompts)):
            dep.step()
        dep.step()
    lanes_idx = jnp.asarray(load.check_lanes)
    jax.block_until_ready(member.last_logits[lanes_idx])


def window(dep, cfg: dict, mix: dict, load: Load, seconds: float,
           span=loops.no_span, slice_=None) -> LMWindow:
    router = dep.router
    member = _member(dep)
    slice_ = slice_ or loops.Slice()
    lanes_idx = jnp.asarray(load.check_lanes)
    positions = np.asarray([member._pos[s] for s in sorted(router.active)])
    counts0 = member.expert_counts.copy()
    kept = []
    n = finished = items = 0
    t0 = time.perf_counter()
    slice_.arm(t0)
    t_end = t0 + seconds
    while True:
        with span("router.step"):
            emitted = dep.step()
        if not emitted and not router.active and not router.queue:
            break                   # every request of the load is done
        items += emitted
        finished += bool(emitted) and emitted == member.last_live
        if n in load.check_steps:
            states = [(router.active.get(lane),
                       int(member._pos[lane]) - 1)
                      for lane in load.check_lanes]
            kept.append((states, member.last_logits[lanes_idx]))
        n += 1
        now = time.perf_counter()
        slice_.poll(now)
        if now >= t_end:
            break
    slice_.close()
    answers = []
    for states, rows in kept:
        for (st, p), row in zip(states, np.asarray(rows)):
            if st is None:          # the lane's request ended this step
                continue
            prompt = np.asarray(st.request.prompt, np.int64)
            emitted_tokens = [int(round(float(o[0]))) for o in st.outputs]
            seq = np.concatenate([prompt, np.asarray(
                emitted_tokens[:p - len(prompt) + 1], np.int64)])
            answers.append((seq, len(prompt), row))
    return LMWindow(window_s=now - t0, attempted=n, finished=finished,
                    items=items, calls=n, steps=n, answers=answers,
                    positions=positions,
                    expert=member.expert_counts - counts0)


def check(cfg: dict, params, load: Load, win: LMWindow):
    """The window's kept logits against the reference's, one reference
    forward per request, up to its last checked position."""
    longest = {}
    for seq, prompt_len, _ in win.answers:
        key = (prompt_len, seq[:prompt_len].tobytes())
        if key not in longest or len(seq) > len(longest[key]):
            longest[key] = seq
    refs = {key: lm_reference.forward(cfg, params, seq, key[0])
            for key, seq in longest.items()}
    ys, ref, near = [], [], []
    for seq, prompt_len, row in win.answers:
        logits, nears = refs[(prompt_len, seq[:prompt_len].tobytes())]
        ys.append(row)
        ref.append(logits[len(seq) - 1])
        near.append(nears[len(seq) - 1])
    vocab = cfg["vocab_size"]
    ys = np.asarray(ys, np.float32).reshape(-1, vocab)
    ref = np.asarray(ref, np.float32).reshape(-1, vocab)
    near = np.asarray(near, bool)
    values = reference.errors(ys, ref, near)
    values["expert_dropped"] = int(win.expert[2])
    note = (f"{len(ys)} logit rows of {len(longest)} requests compared, "
            f"{int(near.sum())} after a near routing tie left out; "
            f"experts {win.expert.tolist()} (assignments, rows, dropped)")
    return values, note


# --------------------------------------------------------------------- #
# work counts, from the published widths
# --------------------------------------------------------------------- #
def _layer_weights(cfg: dict):
    """Weights one decoded token reads in (attention, dense MLP, MoE
    layer) of this chip's share: the absorbed decode's per-head wkv_b
    halves in place of wkv_b; the MoE layer's held experts whole
    (bytes) and at their expected share of a token's top-k (FLOPs)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    attn = d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv + \
        H * dv * d
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    router = d * cfg["published"]["n_routed_experts"]
    held = cfg["n_routed_experts"]
    share = cfg["num_experts_per_tok"] * held / \
        cfg["published"]["n_routed_experts"]
    moe_bytes = router + shared + held * expert
    moe_flops = router + shared + share * expert
    return attn, dense, moe_bytes, moe_flops


def _counts(cfg: dict):
    attn, dense, moe_bytes, moe_flops = _layer_weights(cfg)
    k = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - k
    head = cfg["hidden_size"] * cfg["vocab_size"]
    weights = k * (attn + dense) + n_moe * (attn + moe_bytes) + head
    token_macs = k * (attn + dense) + n_moe * (attn + moe_flops) + head
    return weights, token_macs


def flops(cfg: dict, items: int) -> int:
    """2 x the weight multiply-adds of one token, per token: attention
    over the cache is left out (it depends on the positions)."""
    return int(2 * _counts(cfg)[1] * items)


def min_call_seconds(cfg: dict, mix: dict, chips: int, peaks: dict,
                     positions=None) -> float:
    """One decode step of every lane on one chip. ``positions``: the
    lanes' positions at the window's start (each lane's shortest prompt
    where not given); the latent cache counts only the positions
    written, never the ring's size."""
    lanes = cfg["lanes_per_chip"]
    if positions is None:
        positions = np.full(lanes, min(mix["prompt_lengths"]))
    ctx = float(np.sum(np.asarray(positions) + 1))
    weights, token_macs = _counts(cfg)
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dr, L = cfg["qk_rope_head_dim"], cfg["num_hidden_layers"]
    # scores over c_kv and k_pe, then the attended latent
    attn_flops = 2 * H * (2 * r + dr) * ctx * L
    step_flops = 2 * token_macs * lanes + attn_flops
    cache_bytes = np.dtype(cfg["latent_cache_dtype"]).itemsize
    step_bytes = weights * WEIGHT_BYTES + ctx * (r + dr) * cache_bytes * L
    return max(step_flops / peaks[cfg["peak"]],
               step_bytes / peaks["hbm_bytes_per_s"])


def rehearsal(cfg: dict, mix: dict):
    """A few-layer, 64-wide preset of the same architecture, with its
    own short prompts (``cfg["rehearsal"]``): the cell's own size does
    not fit a CPU test worker."""
    small = cfg["rehearsal"]
    return (dict(cfg, **small["config"],
                 published=dict(cfg["published"], **small["published"])),
            dict(mix, **small["mix"]))

