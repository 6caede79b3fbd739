"""repro.lm — transformer-block mapping + decode-as-streaming.

The exactness contract under test: a dense transformer's seven
per-layer linears programmed onto tile grids (EXACT differential-pair
encoding, ``quantize=False``) must reproduce the dense
``models/transformer.py`` forward at rel ≤ 1e-6 on BOTH systems and
under multi-level Fig. 11 combiner trees, and an LM tenant served
through ``deploy()`` must emit exactly the dense ``serving.Engine``'s
greedy tokens while its stats row sums into the fleet roll-up like any
sensor app."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import qwen1p5_0p5b
from repro.lm import (CompiledLM, LM_LINEARS, TransformerParams,
                      compile_lm, lm_request, tokens_from_state)
from repro.models import model as model_lib


@pytest.fixture(scope="module")
def setup():
    cfg = qwen1p5_0p5b.reduced_serving()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _rel(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12))


def _dense_ref(cfg, params, toks):
    cfg = cfg.replace(decode_per_slot=True)
    logits, cache = jax.jit(
        lambda p, b: model_lib.prefill(cfg, p, b))(params,
                                                   {"tokens": toks})
    return cfg, logits, cache


# ------------------------------------------------------------------- #
# mapped forward == dense forward
# ------------------------------------------------------------------- #
@pytest.mark.parametrize("system,geometry", [
    ("memristor", None),
    ("digital", None),
    # 4-row tiles on d_model=64 → 16 sub-neuron partials per linear →
    # a ≥2-level Fig. 11 combiner tree on the mapped path
    ("memristor", (4, 32)),
])
def test_mapped_matches_dense(setup, system, geometry):
    cfg, params = setup
    clm = compile_lm(TransformerParams(cfg, params), system=system,
                     geometry=geometry)
    if geometry == (4, 32):
        assert any(len(plans[n].levels) >= 2
                   for plans in clm.plans for n in LM_LINEARS)

    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 7))
    dcfg, d_logits, d_cache = _dense_ref(cfg, params, toks)
    m_logits, m_cache = clm.prefill(toks)
    assert _rel(m_logits, d_logits) <= 1e-6
    assert max(_rel(a, b) for a, b in zip(jax.tree.leaves(m_cache),
                                          jax.tree.leaves(d_cache))) \
        <= 1e-6

    # per-slot decode: each lane at its own position
    step = np.asarray([[3], [9]], np.int32)
    pos = np.asarray([7, 7], np.int32)
    d_step, _ = jax.jit(lambda p, c, t, q: model_lib.decode_step(
        dcfg, p, c, t, q))(params, d_cache, step, pos)
    m_step, _ = clm.decode(m_cache, step, pos)
    assert _rel(m_step, d_step) <= 1e-6


def test_compiled_lm_structure(setup):
    cfg, params = setup
    clm = compile_lm(cfg, seed=3, tokens_per_second=10.0)
    assert isinstance(clm, CompiledLM)
    assert len(clm.plans) == cfg.num_layers
    assert all(set(p) == set(LM_LINEARS) for p in clm.plans)
    # the analytic cost chip maps 7 linears per layer as nets
    assert len(clm.chip.mapping.units) == 7 * cfg.num_layers
    assert clm.chip.plan is None            # analytic: no programmed MLP
    rep = clm.report()
    assert rep.area_mm2 > 0 and rep.power_mw > 0
    # seeded compile == dense init with the same seed
    ref = model_lib.init_params(clm.cfg, jax.random.PRNGKey(3))
    assert all(bool(jnp.all(a == b)) for a, b in
               zip(jax.tree.leaves(clm.params), jax.tree.leaves(ref)))


def test_compile_lm_rejects_wrong_inputs(setup):
    cfg, _ = setup
    from repro.core.crossbar_layer import MLPSpec

    with pytest.raises(TypeError, match="ModelConfig or "
                                        "TransformerParams"):
        compile_lm(MLPSpec((4, 2)))
    with pytest.raises(NotImplementedError,
                       match="dense and MoE transformer"):
        compile_lm(cfg.replace(family="ssm"))


def test_compile_chip_points_model_configs_at_compile_lm(setup):
    """Satellite: the sensor compiler names the right entry point when
    handed a transformer config."""
    cfg, _ = setup
    from repro.chip import compile_chip

    with pytest.raises(NotImplementedError,
                       match=r"repro\.lm\.compile_lm"):
        compile_chip(cfg)


# ------------------------------------------------------------------- #
# decode-as-streaming through deploy()
# ------------------------------------------------------------------- #
def _engine_oracle(cfg, params, prompts, n_new, cache_len=64):
    from repro.serving.engine import Engine, Request

    eng = Engine(cfg, params, slots=max(2, len(prompts)),
                 cache_len=cache_len)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=list(p), max_new_tokens=n_new))
    eng.run_until_drained()
    return [st.generated for st in
            sorted(eng.finished, key=lambda st: st.request.uid)]


def test_lm_tenant_tokens_match_dense_engine(setup):
    cfg, params = setup
    from repro.deploy import AppSpec, deploy

    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=64,
                         lanes_per_chip=2))
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (4, 6, 3, 5)]
    for p in prompts:
        assert dep.submit_tokens("lm", p, max_new_tokens=5)
    dep.run_until_drained()
    got = dep.generated_tokens("lm")
    assert len(got) == len(prompts)
    assert all(len(t) == 5 for t in got.values())
    assert [got[uid] for uid in sorted(got)] == \
        _engine_oracle(cfg, params, prompts, 5)
    stats = dep.stats()
    assert stats.apps["lm"].items == stats.fleet.items == 20
    dep.close()


def test_lm_tenant_sensor_verbs_are_guarded(setup):
    cfg, params = setup
    from repro.deploy import AppSpec, deploy

    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=32))
    with pytest.raises(TypeError, match="submit_tokens"):
        dep.submit("lm", np.zeros((3, 1), np.float32))
    with pytest.raises(TypeError, match="submit_tokens"):
        dep.stream("lm", np.zeros((3, 1), np.float32))
    with pytest.raises(NotImplementedError, match="compile_lm"):
        dep.reprogram("lm", params)
    with pytest.raises(ValueError, match="cache_len"):
        dep.submit_tokens("lm", [1, 2, 3], max_new_tokens=40)
    dep.close()

    # and the reverse direction: submit_tokens on a sensor tenant
    from repro.core.crossbar_layer import MLPSpec, mlp_init

    spec = MLPSpec((8, 4), activation="threshold",
                   out_activation="linear")
    dep = deploy(AppSpec("s", spec,
                         params=mlp_init(jax.random.PRNGKey(0), spec)))
    with pytest.raises(TypeError, match="sensor tenant"):
        dep.submit_tokens("s", [1, 2])
    dep.close()


def test_lm_appspec_validation(setup):
    cfg, _ = setup
    from repro.deploy import AppSpec, DeploymentSpec, deploy

    with pytest.raises(ValueError, match="cache_len"):
        AppSpec("lm", cfg, cache_len=1)
    with pytest.raises(ValueError, match="analytic"):
        deploy(DeploymentSpec(apps=(
            AppSpec("lm", cfg, analytic=True),)))


def test_lm_resize_preserves_continuations(setup):
    """Elastic resize mid-decode: evicted LM lanes re-admit by
    re-prefilling prompt + emitted prefix into the rebuilt cache —
    greedy determinism makes the final streams identical to an
    uninterrupted run."""
    cfg, params = setup
    from repro.deploy import AppSpec, deploy

    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=64,
                         lanes_per_chip=2), n_chips=1)
    rng = np.random.default_rng(9)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 4)]
    for p in prompts:
        assert dep.submit_tokens("lm", p, max_new_tokens=6)
    dep.step()
    dep.step()
    dep.resize(1)                       # same size: still evict+requeue
    dep.run_until_drained()
    got = dep.generated_tokens("lm")
    assert [got[uid] for uid in sorted(got)] == \
        _engine_oracle(cfg, params, prompts, 6)
    dep.close()


def test_lm_request_and_state_helpers():
    req = lm_request((1, 2, 3), max_new_tokens=4)
    assert req.prompt == (1, 2, 3)
    assert req.items.shape == (4, 1)
    with pytest.raises(ValueError, match="empty prompt"):
        lm_request(())
    with pytest.raises(ValueError, match="max_new_tokens"):
        lm_request((1,), max_new_tokens=0)

    class _St:
        outputs = [np.asarray([3.0]), np.asarray([7.0])]
    assert tokens_from_state(_St()) == [3, 7]


# ------------------------------------------------------------------- #
# the co-resident duo, end to end (subprocess, 2 simulated devices)
# ------------------------------------------------------------------- #
_DUO_SCRIPT = """
import json
import jax
import numpy as np
from repro.configs import qwen1p5_0p5b
from repro.deploy import AppSpec, DeploymentSpec, deploy
from repro.models import model as model_lib
from repro.serving.engine import Engine, Request

cfg = qwen1p5_0p5b.reduced_serving()
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
dep = deploy(DeploymentSpec(apps=(
    AppSpec("sensor", "deep", items_per_second=100.0, lanes_per_chip=2),
    AppSpec("lm", cfg, params=params, items_per_second=50.0,
            lanes_per_chip=2, cache_len=64),
)))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
           for n in (5, 3, 7, 4)]
for p in prompts:
    assert dep.submit_tokens("lm", p, max_new_tokens=6)
batches = [rng.uniform(0, 1, (3 + i, 784)).astype(np.float32)
           for i in range(3)]
for b in batches:
    assert dep.submit("sensor", b)
dep.run_until_drained()
got = dep.generated_tokens("lm")

eng = Engine(cfg, params, slots=4, cache_len=64)
for i, p in enumerate(prompts):
    eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
eng.run_until_drained()
oracle = [st.generated for st in
          sorted(eng.finished, key=lambda st: st.request.uid)]

s = dep.stats()
rep = dep.report()
print(json.dumps({
    "devices": len(jax.devices()),
    "n_chips": dep.n_chips,
    "token_parity": [got[uid] for uid in sorted(got)] == oracle,
    "lm_items": s.apps["lm"].items,
    "sensor_items": s.apps["sensor"].items,
    "fleet_items": s.fleet.items,
    "lanes_exact": sum(a.lanes for a in s.apps.values())
                   == s.fleet.lanes,
    "requests_exact": sum(a.requests for a in s.apps.values())
                      == s.fleet.requests,
    "report_apps": sorted(rep.apps),
}))
"""


def test_two_device_sensor_lm_duo_subprocess(sim_subprocess):
    res = sim_subprocess(_DUO_SCRIPT, n_devices=2, timeout=900)
    assert res["devices"] == 2 and res["n_chips"] == 2
    assert res["token_parity"]
    assert res["lm_items"] == 4 * 6
    assert res["sensor_items"] == 3 + 4 + 5
    assert res["fleet_items"] == res["lm_items"] + res["sensor_items"]
    assert res["lanes_exact"] and res["requests_exact"]
    assert res["report_apps"] == ["lm", "sensor"]


# ------------------------------------------------------------------- #
# the moe family: latent attention, a dense prefix, held experts
# ------------------------------------------------------------------- #
def _moonlight(**kw):
    """The reduced Moonlight block in float32 with a float32 latent
    cache, so the mapped path meets the dense one at float rounding;
    16 router outputs, 8 of them held here."""
    from repro.configs import moonshot_v1_16b_a3b

    return moonshot_v1_16b_a3b.reduced().replace(
        compute_dtype="float32", decode_per_slot=True, num_experts=16,
        top_k=4, held_experts=tuple(range(8)), kv_cache_dtype="float32",
        **kw)


@pytest.fixture(scope="module")
def moe_setup():
    cfg = _moonlight()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(4))
    return cfg, params


def _full_logits(cfg, params, toks):
    """Every position's logits of the dense forward (no cache)."""
    with jax.default_matmul_precision("highest"):
        h, _, _ = model_lib.forward(cfg, params, {"tokens": toks},
                                    mode="train")
        return np.asarray(model_lib._head(cfg, params, h))


def test_moe_mla_prefill_then_decode_matches_full_forward(moe_setup):
    """compile_lm on the moe family: prefill, then decode through the
    latent cache lane by lane, against the dense model's full forward
    over the same tokens (logits)."""
    cfg, params = moe_setup
    clm = compile_lm(TransformerParams(cfg, params))
    assert set(clm.plans[0]) == {"wq", "wkv_a", "wkv_b", "wo", "wkv_b.k",
                                 "wkv_b.v", "w1", "w3", "w2"}
    assert {"router", "experts.w1", "shared.w2"} <= set(clm.plans[1])
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 12))
    full = _full_logits(cfg, params, toks)
    logits, cache = clm.prefill(toks[:, :8])
    assert _rel(logits, full[:, 7]) <= 1e-6
    ring = clm.init_cache(2, 16, jnp.float32)
    from repro.serving import kvcache
    for lane in range(2):
        one = jax.tree.map(lambda x, i=lane: x[:, i:i + 1], cache)
        ring = kvcache.write_slot(ring, one, jnp.int32(lane))
    for pos in range(8, 12):
        nxt, logits, counts, ring = clm.step(
            ring, toks[:, pos:pos + 1], np.full((2,), pos))
        assert _rel(logits, full[:, pos]) <= 1e-6
        assert np.array_equal(np.asarray(nxt),
                              np.argmax(np.asarray(logits), -1))
        # two MoE layers, 2 lanes, 8 held experts: 32 rows, none dropped
        assert counts.tolist()[1:] == [2 * 2 * 8, 0]
        assert 0 < int(counts[0]) <= 2 * 2 * cfg.top_k


def test_absorbed_decode_matches_expanded_attention():
    """One latent-attention layer: the decode step's absorbed form
    (q_nope into the latent through wkv_b's key half, the output back
    through its value half) against the prefill's expanded K/V at the
    same position."""
    from repro.models import attention as attn

    cfg = _moonlight()
    p = attn.mla_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    with jax.default_matmul_precision("highest"):
        full, _ = attn.mla_apply(p, cfg, x, positions=pos, mode="train")
        _, cache = attn.mla_apply(p, cfg, x[:, :8], positions=pos[:, :8],
                                  mode="prefill")
        cache = jax.tree.map(
            lambda c: jnp.pad(c, ((0, 0), (0, 4), (0, 0))), cache)
        step, new = attn.mla_apply(p, cfg, x[:, 8:], positions=pos[:, 8:],
                                   mode="decode", cache=cache)
    assert _rel(step[:, 0], full[:, 8]) <= 1e-5
    # the cache holds the latent and the roped key, nothing expanded
    assert new["c_kv"].shape == (2, 12, cfg.kv_lora_rank)
    assert new["k_pe"].shape == (2, 12, cfg.qk_rope_head_dim)


def _full_ring_decode(p, cfg, x, positions, cache, layer=None):
    """The latent decode over every one of the ring's T slots, as it
    was before the prefix: the oracle of the prefix's answer."""
    from repro.models import attention as attn
    from repro.models.layers import apply_rope, rms_norm

    dt = x.dtype
    B = x.shape[0]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = (dn + dr) ** -0.5
    project = attn._mla_dense_projector(cfg, p)
    q = project("wq", x).reshape(B, 1, H, dn + dr)
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    kv = project("wkv_a", x)
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    pos_b = positions[:, 0]
    T = cache["c_kv"].shape[-2]
    idx, bidx = pos_b % T, jnp.arange(B)
    full_c, c_all = attn._slot_write(cache["c_kv"], layer, bidx, idx, c[:, 0])
    full_pe, pe_all = attn._slot_write(cache["k_pe"], layer, bidx, idx,
                                       k_pe[:, 0])
    k_pos = jax.vmap(attn._ring_positions, (0, None))(pos_b, T)
    q_lat = project("wkv_b.k", q[..., :dn].reshape(B, H, dn))
    scores = (jnp.einsum("bhr,btr->bht", q_lat, c_all) +
              jnp.einsum("bhe,bte->bht", q_pe[:, 0], pe_all)) * scale
    mask = (k_pos <= pos_b[:, None]) & (k_pos >= 0)
    w = attn._masked_softmax(scores, mask[:, None, :])
    o_lat = jnp.einsum("bht,btr->bhr", w.astype(dt), c_all)
    out = project("wkv_b.v", o_lat).reshape(B, 1, H * dv)
    return project("wo", out), {"c_kv": full_c, "k_pe": full_pe}


# ring of four whole blocks and a short fifth; the lanes' positions:
# a block's first and last slot, inside the fourth, the ring's last
# slot, and one lane that has wrapped
_BLOCK = 512
_RING = 4 * _BLOCK + 100
_LANE_POS = (0, _BLOCK - 1, _BLOCK, 3 * _BLOCK + 5, _RING - 1, _RING + 7)


@pytest.mark.parametrize("layer", [None, 1])
@pytest.mark.parametrize("lanes,blocks", [(1, 1), (2, 1), (3, 2), (4, 4),
                                          (5, 5), (6, 5)])
def test_latent_decode_prefix_matches_the_full_ring(lanes, blocks, layer):
    """mla_apply's decode attends over the ring's first written blocks
    only; with the lanes at mixed positions (the newest one setting
    the prefix, a wrapped one the whole ring) it matches the full-ring
    formula, output and written cache, and the unwritten slots, filled
    with noise here, take no part; ``stats`` gets the taken branch's
    slots and the ring's."""
    from repro.models import attention as attn

    assert attn.DECODE_BLOCK == _BLOCK
    cfg = _moonlight()
    p = attn.mla_init(jax.random.PRNGKey(1), cfg)
    pos = jnp.asarray(_LANE_POS[:lanes], jnp.int32)[:, None]
    ends, i = attn._decode_prefix(pos[:, 0], _RING)
    assert int(i) + 1 == blocks
    x = jax.random.normal(jax.random.PRNGKey(2), (lanes, 1, cfg.d_model))
    lead = (lanes, _RING) if layer is None else (2, lanes, _RING)
    ks = jax.random.split(jax.random.PRNGKey(3))
    cache = {"c_kv": jax.random.normal(ks[0], lead + (cfg.kv_lora_rank,)),
             "k_pe": jax.random.normal(ks[1],
                                       lead + (cfg.qk_rope_head_dim,))}
    stats = []
    with jax.default_matmul_precision("highest"):
        got, got_cache = attn.mla_apply(p, cfg, x, positions=pos,
                                        mode="decode", cache=cache,
                                        layer=layer, stats=stats)
        want, want_cache = _full_ring_decode(p, cfg, x, pos, cache, layer)
    # float32 rounding of a shorter reduction, relative to the largest
    assert _rel(got, want) <= 1e-6
    assert [s.tolist() for s in stats] == [[ends[blocks - 1], _RING]]
    for name in ("c_kv", "k_pe"):
        np.testing.assert_array_equal(np.asarray(got_cache[name]),
                                      np.asarray(want_cache[name]))


@pytest.mark.parametrize("ring,newest,blocks", [
    (4096, 0, 1), (4096, 511, 1), (4096, 512, 2), (4096, 1023, 2),
    (4096, 1024, 3), (4096, 2559, 5), (4096, 2560, 6), (4096, 4095, 8),
    (4096, 4096, 8), (4096, 70000, 8), (2148, 2047, 4), (2148, 2048, 5),
    (2148, 2147, 5), (2148, 2148, 5), (600, 599, 2), (64, 63, 1),
    (64, 64, 1)])
def test_written_slots_at_the_block_boundaries(ring, newest, blocks):
    """The fewest blocks from slot 0 that hold every lane's written
    slots: the newest lane's position + 1, over the block, rounded up;
    the whole ring (a short last block counted) once a lane wraps;
    numpy positions (the host's counter) and jax ones (the step) alike;
    the prefixes are whole blocks, the last one cut at the ring."""
    from repro.models.attention import DECODE_BLOCK, _decode_prefix

    block = min(DECODE_BLOCK, ring)
    pos = np.asarray([0, newest // 2, newest], np.int32)
    ends, i = _decode_prefix(pos, ring)
    assert ends == [min(n * block, ring) for n in range(1, len(ends) + 1)]
    assert ends[-1] == ring and int(i) + 1 == blocks
    assert int(jax.jit(lambda q: _decode_prefix(q, ring)[1])(
        jnp.asarray(pos))) + 1 == blocks


def test_lm_member_counts_the_prefix_and_frees_lanes_at_zero(moe_setup):
    """A latent-attention member counts, per decode step and layer,
    the slots its decode's branch read per lane (``lm.attn_slots_read``:
    one 512-slot block while every lane is short) and the ring
    (``lm.attn_slots_ring``); a released lane's position is 0, so a
    free lane does not hold the prefix at the ring's length."""
    from repro import obs
    from repro.deploy import AppSpec, deploy
    from repro.models.attention import _decode_prefix

    cfg, params = moe_setup
    ring = 1100
    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=ring,
                         lanes_per_chip=2))
    member = dep.router.members["lm"]
    rng = np.random.default_rng(8)
    for n in (5, 7):
        assert dep.submit_tokens("lm", list(rng.integers(
            0, cfg.vocab_size, size=n)), max_new_tokens=3)
    tel = obs.configure(metrics=True, trace=False)
    try:
        steps = 0
        while dep.router.active or dep.router.queue:
            dep.step()
            steps += member.last_live > 0
        counters = tel.metrics.snapshot()["counters"]
    finally:
        obs.disable()
        dep.close()
    assert steps > 0
    layers = cfg.num_layers
    assert counters["lm.attn_slots_read"] == 512 * layers * steps
    assert counters["lm.attn_slots_ring"] == ring * layers * steps
    assert member._pos.tolist() == [0, 0]
    assert int(_decode_prefix(member._pos, ring)[1]) == 0


def test_route_sigmoid_bias_normalise_scale():
    """DeepSeek-V3 routing by hand: selection ranks sigmoid + bias, the
    weights are the unbiased sigmoid scores of the chosen, normalised
    and scaled."""
    from repro.models import moe

    cfg = _moonlight().replace(num_experts=4, top_k=2, router_scale=2.5)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.6, 0.0])
    w, ids, scores = moe.route(cfg, logits, bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    # s + bias = [.881, .731, 1.1, .269]: experts 2 then 0
    assert ids.tolist() == [[2, 0]]
    want = np.asarray([s[2], s[0]]) / (s[2] + s[0]) * 2.5
    np.testing.assert_allclose(np.asarray(w[0]), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scores[0]), s, rtol=1e-6)
    # without the bias the top two are experts 0 and 1
    _, ids, _ = moe.route(cfg, logits)
    assert ids.tolist() == [[0, 1]]


def test_held_expert_shares_sum_to_the_uncut_layer():
    """Guide §4: at a small size, the shares of chips that each hold
    a slice of the experts, with the shared expert counted once, add
    up to the whole layer computed uncut (the capacity dispatch with
    every expert, drop-free at this capacity factor)."""
    from repro.models import moe

    whole = _moonlight().replace(held_experts=())
    p = moe.moe_init(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, whole.d_model))
    with jax.default_matmul_precision("highest"):
        y_all, _ = moe.moe_apply(p, whole, x)
        shared = moe.shared_mlp(p, whole, x)
        total = -(whole.num_experts // 8 - 1) * shared
        for chip in range(whole.num_experts // 8):
            held = tuple(range(8 * chip, 8 * chip + 8))
            share = dict(p, **{k: p[k][8 * chip:8 * chip + 8]
                               for k in ("w1", "w3", "w2")})
            y, aux = moe.moe_apply(share, whole.replace(held_experts=held),
                                   x)
            assert float(aux["drop_frac"]) == 0.0
            total = total + y
    assert _rel(total, y_all) <= 1e-5


def test_kvcache_slot_surgery_moves_one_latent_lane():
    """write_slot / clear_slot on the latent cache (c_kv, k_pe): the
    lane written holds the prefilled latents, every other lane is
    untouched."""
    from repro.serving import kvcache

    cfg = _moonlight()
    cache = model_lib.init_cache(cfg, 3, 8, jnp.float32)
    assert set(cache) == {"c_kv", "k_pe"}
    rng = np.random.default_rng(0)
    cache = jax.tree.map(
        lambda c: jnp.asarray(rng.standard_normal(c.shape), c.dtype), cache)
    before = jax.tree.map(np.asarray, cache)
    one = jax.tree.map(
        lambda c: jnp.asarray(rng.standard_normal(
            (c.shape[0], 1, 5) + c.shape[3:]), c.dtype), cache)
    out = kvcache.write_slot(cache, one, jnp.int32(1))
    for name in ("c_kv", "k_pe"):
        got = np.asarray(out[name])
        np.testing.assert_array_equal(got[:, 1, :5],
                                      np.asarray(one[name])[:, 0])
        np.testing.assert_array_equal(got[:, 1, 5:], before[name][:, 1, 5:])
        np.testing.assert_array_equal(got[:, [0, 2]], before[name][:, [0, 2]])
    out = kvcache.clear_slot(out, jnp.int32(2))
    for name in ("c_kv", "k_pe"):
        got = np.asarray(out[name])
        assert not got[:, 2].any()
        np.testing.assert_array_equal(got[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(got[:, 1, :5],
                                      np.asarray(one[name])[:, 0])


def test_moe_tenant_serves_through_deploy(moe_setup):
    """deploy() → compile_lm → LMMember → the router for the moe
    family: greedy tokens equal the dense engine's over the same
    weights, and the step counts expert rows without a drop."""
    cfg, params = moe_setup
    from repro.deploy import AppSpec, deploy

    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=32,
                         lanes_per_chip=2))
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 7, 4)]
    for p in prompts:
        assert dep.submit_tokens("lm", p, max_new_tokens=4)
    dep.run_until_drained()
    got = dep.generated_tokens("lm")
    with jax.default_matmul_precision("highest"):
        oracle = _engine_oracle(cfg, params, prompts, 4, cache_len=32)
    assert [got[uid] for uid in sorted(got)] == oracle
    member = dep.router.members["lm"]
    assigned, rows, dropped = member.expert_counts.tolist()
    assert rows > 0 and 0 < assigned <= rows and dropped == 0
    assert member.cache["c_kv"].dtype == jnp.float32
    dep.close()



# ------------------------------------------------------------------- #
# one decode step in flight
# ------------------------------------------------------------------- #
def _sync_stream_host(self, batch, *, use_kernel=False):
    """The member's decode as a plain synchronous loop over
    ``CompiledLM.serve_step``: emit the staged tokens, decode them, wait
    for the next ones and stage them."""
    out = self._staged.copy()
    nxt, self.last_logits, _, _, self.cache = self.clm.serve_step(
        self.cache, self._staged[:, None], self._pos,
        use_kernel=use_kernel)
    nxt = np.asarray(nxt)
    self.last_live = len(self._live)
    for lane in self._live:
        self._staged[lane] = nxt[lane]
        self._pos[lane] += 1
    return out.astype(np.float32)[:, None]


def _recorded(dep, stream_host):
    """Serve through ``stream_host`` and record, per call, the live
    lanes with the tokens they emit and their logits rows."""
    member = dep.router.members["lm"]
    calls = []

    def call(batch, *, use_kernel=False):
        lanes = sorted(member._live)
        out = stream_host(batch, use_kernel=use_kernel)
        calls.append((lanes, out[lanes, 0].tolist(),
                      np.asarray(member.last_logits)[lanes]))
        return out
    member.stream_host = call
    return calls


def _lm_tenant(request, name):
    return request.getfixturevalue({"gqa": "setup", "moe": "moe_setup"}[name])


@pytest.mark.parametrize("tenant", ["gqa", "moe"])
def test_overlapped_decode_matches_a_synchronous_loop(request, tenant):
    """Admissions mid-stream, releases at different lengths and an
    elastic resize: the member that keeps one step in flight emits the
    synchronous loop's tokens and binds ``last_logits`` to the same
    step, row for row."""
    from repro.deploy import AppSpec, deploy

    cfg, params = _lm_tenant(request, tenant)
    rng = np.random.default_rng(12)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 3, 6, 4, 7)]
    new = (3, 6, 4, 5, 2)
    runs = []
    for overlapped in (True, False):
        dep = deploy(AppSpec("lm", cfg, params=params, cache_len=32,
                             lanes_per_chip=3))
        member = dep.router.members["lm"]
        calls = _recorded(dep, member.stream_host if overlapped else
                          _sync_stream_host.__get__(member))
        for p, n in zip(prompts[:3], new[:3]):
            assert dep.submit_tokens("lm", p, max_new_tokens=n)
        for _ in range(2):
            dep.step()
        for p, n in zip(prompts[3:], new[3:]):
            assert dep.submit_tokens("lm", p, max_new_tokens=n)
        for _ in range(3):
            dep.step()
        dep.resize(1)
        dep.run_until_drained()
        got = dep.generated_tokens("lm")
        runs.append(([got[uid] for uid in sorted(got)], calls,
                     member.decode_steps))
        dep.close()
    (tokens, calls, steps), (tokens_sync, calls_sync, _) = runs
    assert tokens == tokens_sync
    assert [len(t) for t in tokens] == list(new)
    assert steps == len(calls) == len(calls_sync)
    for (lanes, out, rows), (lanes_s, out_s, rows_s) in zip(calls,
                                                            calls_sync):
        assert lanes == lanes_s and out == out_s
        np.testing.assert_array_equal(rows, rows_s)


def _aligned(a):
    """A copy of ``a`` on a 64-byte boundary, where the CPU client
    hands a jitted call the host buffer itself instead of a copy."""
    buf = np.zeros(a.size + 64 // a.itemsize, a.dtype)
    off = (-buf.ctypes.data % 64) // a.itemsize
    out = buf[off:off + a.size]
    out[:] = a
    return out


class _BumpAfterDispatch:
    """A CompiledLM whose ``serve_step`` moves the member's host
    positions on as soon as the step is dispatched."""

    def __init__(self, clm, member):
        self._clm, self._member = clm, member

    def __getattr__(self, name):
        return getattr(self._clm, name)

    def serve_step(self, *args, **kw):
        res = self._clm.serve_step(*args, **kw)
        self._member._pos += 1000
        return res


@pytest.mark.parametrize("tenant", ["gqa", "moe"])
def test_positions_bumped_after_dispatch_miss_the_step_in_flight(
        request, tenant):
    from repro.deploy import AppSpec, deploy

    cfg, params = _lm_tenant(request, tenant)
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 3)]
    rows = []
    for bump in (False, True):
        dep = deploy(AppSpec("lm", cfg, params=params, cache_len=32,
                             lanes_per_chip=2))
        member = dep.router.members["lm"]
        for p in prompts:
            assert dep.submit_tokens("lm", p, max_new_tokens=4)
        dep.step()
        member._pos = _aligned(member._pos)
        if bump:
            member.clm = _BumpAfterDispatch(member.clm, member)
        dep.step()
        rows.append(np.asarray(member.last_logits))
        dep.close()
    np.testing.assert_array_equal(rows[1], rows[0])


@pytest.mark.parametrize("tenant", ["gqa", "moe"])
def test_decode_counters_count_every_step_and_the_overlapped(request,
                                                             tenant):
    """``lm.decode_dispatched`` counts every step; ``lm.decode_overlapped``
    counts a step whose predecessor's tokens were still being computed
    (here held back by a slow program) and not one whose predecessor
    had finished."""
    from repro import obs
    from repro.deploy import AppSpec, deploy

    cfg, params = _lm_tenant(request, tenant)

    @jax.jit
    def slow(tokens):
        x = jax.lax.fori_loop(0, 3000, lambda i, a: jnp.sin(a) + 1.0,
                              jnp.ones((1 << 16,), jnp.float32))
        return tokens + (jnp.max(x) > 1e30).astype(jnp.int32)

    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=32,
                         lanes_per_chip=2))
    member = dep.router.members["lm"]
    rng = np.random.default_rng(14)
    for n in (5, 3):
        assert dep.submit_tokens("lm", list(rng.integers(
            0, cfg.vocab_size, size=n)), max_new_tokens=8)
    dep.step()
    jax.block_until_ready(slow(member._flight[0]))      # compiled
    tel = obs.configure(metrics=True, trace=False)
    try:
        for held in (False, True, False, True, True):
            nxt, *rest = member._flight
            if held:
                member._flight = (slow(nxt), *rest)
            else:
                jax.block_until_ready(nxt)
            dep.step()
        counters = tel.metrics.snapshot()["counters"]
    finally:
        obs.disable()
    steps = member.decode_steps
    dep.run_until_drained()
    got = dep.generated_tokens("lm")
    dep.close()
    assert steps == 6
    assert counters["lm.decode_dispatched"] == 5
    assert counters["lm.decode_overlapped"] == 3
    assert all(len(t) == 8 for t in got.values())


def test_lm_above_the_routed_tile_limit_is_served_unpriced(setup,
                                                          monkeypatch):
    """Above ``ROUTED_TILE_LIMIT`` tiles compile_lm builds no cost chip:
    the tenant is served, left out of the report, and refuses a
    tokens/second SLO it could not check."""
    from repro.deploy import AppSpec, deploy
    from repro.lm import compile as lm_compile

    cfg, params = setup
    monkeypatch.setattr(lm_compile, "ROUTED_TILE_LIMIT", 1)
    clm = compile_lm(TransformerParams(cfg, params))
    assert clm.chip is None
    with pytest.raises(ValueError, match="not priced"):
        clm.report()
    dep = deploy(AppSpec("lm", cfg, params=params, cache_len=16,
                         lanes_per_chip=1))
    assert dep.submit_tokens("lm", [1, 2, 3], max_new_tokens=2)
    dep.run_until_drained()
    assert len(dep.generated_tokens("lm")) == 1
    assert "lm" not in dep.report().apps
    dep.close()
    with pytest.raises(ValueError, match="tokens/second"):
        deploy(AppSpec("lm", cfg, params=params, cache_len=16,
                       lanes_per_chip=1, items_per_second=10.0))
