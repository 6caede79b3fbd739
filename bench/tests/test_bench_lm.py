"""CPU tests of the LM tenant (``bench/tenants/lm.py``) and its plain
reference (``bench/lm_reference.py``), at the tenant's rehearsal size:
the check fails on an altered logit, on a dropped token and on the
reference one step lower in precision; the reference's bfloat16 cache
is what the program's cache holds; the work counts follow the
published widths."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import catalog, lm_reference, reference
from bench.tests.test_bench_harness import harness  # noqa: F401

CELL = "moonlight-16b-a3b.decode"


def _tenant():
    cfg = catalog.config("moonlight-16b-a3b")
    tenant = catalog.tenant(cfg["tenant"])
    return cfg, tenant


def _rehearsed(**over):
    cfg, tenant = _tenant()
    small, mix = tenant.rehearsal(cfg, catalog.mix("decode"))
    return dict(small, **over), mix, tenant


def _served(cfg, mix, tenant, seed=11, seconds=0.3):
    params = tenant.make_params(cfg, seed)
    dep = tenant.deploy(cfg, params, 1)
    load = tenant.make_load(cfg, mix, seed, seconds)
    tenant.warm(dep, cfg, load)
    win = tenant.window(dep, cfg, mix, load, seconds)
    dep.close()
    return params, load, win


@pytest.fixture(scope="module")
def served():
    cfg, mix, tenant = _rehearsed()
    return (cfg, mix, tenant) + _served(cfg, mix, tenant)


def test_window_is_decode_only_and_checks(served):
    cfg, mix, tenant, params, load, win = served
    assert win.attempted == win.finished > 0
    assert win.items == win.finished * cfg["lanes_per_chip"]
    assert len(win.answers) > 0
    values, note = tenant.check(cfg, params, load, win)
    limits = cfg["check"]["limits"]
    assert all(values[k] <= limits[k] for k in limits), (values, note)
    assert values["expert_dropped"] == 0
    assigned, rows, dropped = win.expert.tolist()
    assert 0 < assigned < rows and dropped == 0


def test_altered_logit_fails_the_check(served):
    cfg, mix, tenant, params, load, win = served
    seq, prompt_len, row = win.answers[0]
    row = row.copy()
    row[int(np.argmax(np.abs(row)))] *= 1.001
    win.answers[0] = (seq, prompt_len, row)
    try:
        values, _ = tenant.check(cfg, params, load, win)
    finally:
        win.answers[0] = (seq, prompt_len, row / 1.001)
    assert values["rel_err"] > cfg["check"]["limits"]["rel_err"]


def test_reference_one_step_lower_fails_the_limit(served):
    """The control: the reference one step lower in precision, in the
    program's place, reads above a limit. The chip's control is the
    TPU's HIGH (``check.control``); the CPU's HIGH is exact, so here
    the matmuls take bfloat16 operands."""
    cfg, mix, tenant, params, load, win = served
    limits = cfg["check"]["limits"]
    for seq, prompt_len, _ in win.answers[:2]:
        ref, near = lm_reference.forward(cfg, params, seq, prompt_len)
        ctl, _ = lm_reference.forward(cfg, params, seq, prompt_len,
                                      mode="bf16")
        assert all(v == 0.0 for v in reference.errors(ref, ref,
                                                      near).values())
        read = reference.errors(ctl, ref, near)
        assert any(read[k] > limits[k] for k in ("rel_err", "rms_err"))


def test_dropped_token_fails(harness, monkeypatch):  # noqa: F811
    """A dispatch that loses one token-expert pair: the counter reads
    it and ``correct`` is false."""
    from repro.models import moe

    real = moe.held_dispatch

    def lossy(cfg, w, ids):
        order, gates, stats = real(cfg, w, ids)
        gates = gates.at[0, 0].set(0.0)
        return order, gates, dict(stats, dropped=stats["dropped"] + 1)

    monkeypatch.setattr(moe, "held_dispatch", lossy)
    jax.clear_caches()
    try:
        rc, res, err = harness(CELL)
    finally:
        jax.clear_caches()
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["expert_dropped"]["value"] > 0


def test_reference_reads_the_bf16_cache_as_the_program_does():
    """With a bfloat16 latent cache the program's decode logits lie far
    nearer the reference that rounds what decoding queries read than
    the one that does not."""
    cfg, mix, tenant = _rehearsed(latent_cache_dtype="bfloat16")
    params, load, win = _served(cfg, mix, tenant, seed=5)
    exact = dict(cfg, latent_cache_dtype="float32")
    gaps = {}
    for name, c in (("bf16", cfg), ("f32", exact)):
        ys, refs = [], []
        for seq, prompt_len, row in win.answers:
            ref, _ = lm_reference.forward(c, params, seq, prompt_len)
            ys.append(row)
            refs.append(ref[len(seq) - 1])
        gaps[name] = reference.errors(np.asarray(ys), np.asarray(refs),
                                      np.zeros(len(ys), bool))["rms_err"]
    assert gaps["bf16"] * 10 < gaps["f32"], gaps


def test_near_routing_ties_are_left_out():
    cfg, mix, tenant = _rehearsed()
    params = tenant.make_params(cfg, 3)
    toks = np.arange(20) % cfg["vocab_size"]
    _, near = lm_reference.forward(cfg, params, toks, 12)
    assert not near.any()
    wide = dict(cfg, check=dict(cfg["check"], near_margin=10.0))
    _, near = lm_reference.forward(wide, params, toks, 12)
    assert near.all()
    ref = np.ones((3, 4), np.float32)
    assert reference.errors(ref, ref, np.ones(3, bool))["rel_err"] == \
        float("inf")


def test_work_counts_follow_the_published_widths():
    cfg, tenant = _tenant()
    d, H = 2048, 16
    attn = d * H * 192 + d * 576 + H * 128 * 512 + H * 512 * 128 + \
        H * 128 * d
    expert = 3 * d * 1408
    moe_bytes = d * 64 + 2 * expert + 8 * expert
    moe_macs = d * 64 + 2 * expert + 6 * 8 / 64 * expert
    head = d * 20480
    weights = attn + 3 * d * 11264 + 4 * (attn + moe_bytes) + head
    macs = attn + 3 * d * 11264 + 4 * (attn + moe_macs) + head
    assert tenant._counts(cfg) == (weights, macs)
    assert tenant.flops(cfg, 3) == int(2 * macs * 3)
    peaks = catalog.peaks("TPU v5 lite")
    mix = catalog.mix("decode")
    at = np.full(128, 1536)
    t = tenant.min_call_seconds(cfg, mix, 1, peaks, positions=at)
    cache = 128 * 1537 * 576 * 4 * 5           # float32 latents
    assert t == pytest.approx((4 * weights + cache) / 819e9)
    # no positions: each lane at the shortest prompt, never the ring
    lo = tenant.min_call_seconds(cfg, mix, 1, peaks)
    assert lo < t < tenant.min_call_seconds(
        cfg, mix, 1, peaks, positions=np.full(128, 4095))


def test_prompt_lengths_are_drawn_per_request():
    cfg, tenant = _tenant()
    mix = catalog.mix("decode")
    a = tenant.make_load(cfg, mix, 2**40 + 7, 10.0)
    b = tenant.make_load(cfg, mix, 2**40 + 7, 10.0)
    c = tenant.make_load(cfg, mix, 2**40 + 8, 10.0)
    assert [p.tolist() for p in a.prompts] == [p.tolist() for p in b.prompts]
    lanes = cfg["lanes_per_chip"]
    blocks = []
    for load in (a, c):
        lengths = [len(p) for p in load.prompts]
        assert set(lengths) == set(mix["prompt_lengths"])
        assert all(len(p) + n == cfg["cache_len"]
                   for p, n in zip(load.prompts, load.max_new))
        assert max(int(p.max()) for p in load.prompts) < cfg["vocab_size"]
        assert len(load.check_lanes) == mix["checked_lanes"] == 8
        assert len(load.check_steps) == mix["checked_steps"] == 4
        blocks += [tuple(np.bincount(lengths[i:i + lanes], minlength=2049)
                         [mix["prompt_lengths"]])
                   for i in range(0, len(lengths), lanes)]
    # independent draws: the blocks of a lane's worth of requests do not
    # all hold the same multiset of lengths
    assert len(set(blocks)) > 1
    assert [len(p) for p in a.prompts] != [len(p) for p in c.prompts]


def test_warm_compiles_every_prompt_length():
    """A load whose first admissions miss a prompt length: the warm-up
    still compiles it, so the window, which admits it, compiles
    nothing."""
    from bench import run

    cfg, mix, tenant = _rehearsed()
    params = tenant.make_params(cfg, 13)
    dep = tenant.deploy(cfg, params, 1)
    load = tenant.make_load(cfg, mix, 13, 0.3)
    short, late = min(mix["prompt_lengths"]), max(mix["prompt_lengths"])
    # short requests of two tokens each, the last one long: the warm-up
    # serves the first lanes' worth twice, the window admits the rest
    prompts = [p[:short] for p in load.prompts[:-1]] + [np.ones(late)]
    prompts = tuple(np.asarray(p, np.int64) for p in prompts)
    load = dataclasses.replace(load, prompts=prompts,
                               max_new=tuple(2 for _ in prompts))
    member = dep.router.members[tenant.APP]
    tenant.warm(dep, cfg, load)
    assert member.prefill_tokens < sum(map(len, prompts))
    counter = run.CompileCounter()
    try:
        win = tenant.window(dep, cfg, mix, load, 5.0)
    finally:
        counter.close()
        dep.close()
    assert counter.count == 0
    # every request admitted, the long one too, after one warm-up
    # request of each length
    assert member.prefill_tokens == sum(map(len, prompts)) + short + late
    assert win.attempted > 0


def test_weights_are_drawn_in_the_programs_layout():
    """The benchmark's tree has the structure and shapes of the
    program's own (``init_params``), values its own: a missing or
    zero correction bias, or unit norms, would not pass unseen."""
    from repro.models import model as model_lib

    cfg, mix, tenant = _rehearsed()
    params = tenant.make_params(cfg, 7)
    want = jax.eval_shape(lambda k: model_lib.init_params(
        tenant.model_config(cfg), k), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert jax.tree.structure(got) == jax.tree.structure(
        jax.tree.map(lambda a: (a.shape, a.dtype), want))
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    bias = np.asarray(params["stack"]["moe"]["mlp"]["router_bias"])
    assert np.all(bias != 0) and np.abs(bias).max() < 0.1
    norm = np.asarray(params["stack"]["dense"]["attn_norm"])
    assert np.all((norm >= 0.8) & (norm <= 1.2)) and norm.std() > 0.05
    again = tenant.make_params(cfg, 7)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))


def test_reference_reads_the_router_bias():
    """The reference with the correction bias zeroed chooses other
    experts and reads above the limits: a path that ignored the bias
    would fail the check."""
    cfg, mix, tenant = _rehearsed()
    params = tenant.make_params(cfg, 3)
    toks = np.arange(40) % cfg["vocab_size"]
    ref, near = lm_reference.forward(cfg, params, toks, 24)
    moe = params["stack"]["moe"]
    unbiased = dict(params, stack=dict(params["stack"], moe=dict(
        moe, mlp=dict(moe["mlp"],
                      router_bias=0 * moe["mlp"]["router_bias"]))))
    off, _ = lm_reference.forward(cfg, unbiased, toks, 24)
    read = reference.errors(off, ref, np.zeros(len(toks), bool))
    limits = cfg["check"]["limits"]
    assert all(read[k] > limits[k] for k in ("rel_err", "rms_err")), read


def test_config_at_its_published_values_is_the_registry_model():
    """The cell's file, with every cut key back at its published value
    and every expert held, builds ``configs/moonshot_v1_16b_a3b``'s
    CONFIG: the registry's model is the catalog row, key for key."""
    from repro.configs import moonshot_v1_16b_a3b as moonshot

    cfg, tenant = _tenant()
    full = dict(cfg, **cfg["published"],
                held_experts=list(range(cfg["published"]
                                        ["n_routed_experts"])))
    built = tenant.model_config(full)
    want = moonshot.CONFIG
    assert set(cfg["reduced"]) == set(cfg["published"])
    for field in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "head_dim", "d_ff", "vocab_size", "rope_theta",
                  "norm_eps", "num_experts", "top_k", "num_shared_experts",
                  "router_score", "router_bias",
                  "router_scale", "first_dense_layers", "dense_d_ff",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "tie_embeddings", "act"):
        assert getattr(built, field) == getattr(want, field), field
    assert built.n_held_experts == want.n_held_experts == 64
