"""Decode-as-streaming: the LM tenant as an ordinary router member.

One decode step IS one streamed item. An :class:`LMRequest` rides the
SAME :class:`repro.serving.KeyedItemStreamScheduler` lane block that
serves sensor frames — its ``items`` placeholder is
``(max_new_tokens, 1)``, so the scheduler's per-item accounting
(items/requests/rejections, latency reservoirs, per-app stats rows
that sum exactly to the fleet roll-up) counts TOKENS with zero new
bookkeeping. The router's member hooks bind the lane lifecycle to the
KV cache:

  admit   → B=1 prefill of the prompt, ``kvcache.write_slot`` into the
            lane, first greedy token staged
  step    → emit the staged token, then ONE batched ``CompiledLM.decode``
            over every lane at its own position (inactive lanes decode
            junk that ring-position masking ignores and the next admit
            overwrites — same discipline as ``serving.Engine``)
  release → ``kvcache.clear_slot``

Re-admission after an eviction (elastic resize / requeue) re-prefills
prompt + already-emitted tokens: greedy decoding is deterministic, so
the continuation picks up exactly where the evicted lane stopped, and
nothing is re-emitted (the scheduler's ``pos`` survives the trip).

Token telemetry rides the ``repro.obs`` registry: ``lm.tokens``
(counter, one per live lane per step), ``lm.prefill_tokens`` and a
per-token ``lm.decode_latency_s`` histogram. Spans: ``lm.prefill`` (one
admission: prefill, slot write, first token on the host) and
``lm.decode`` (the batched decode, from dispatch to the tokens on the
host). A MoE tenant's dispatch counts come back with the step's tokens
in the same read-back, never by a sync of their own:
``lm.expert_assignments`` (token-expert pairs on held experts),
``lm.expert_rows`` (rows the grouped expert layer computed) and
``lm.expert_dropped`` (pairs that found no slot; 0 by construction).
A latent-attention tenant also counts, per step and summed over its
layers, the ring slots its decode's branch read per lane and the
ring's slots: ``lm.attn_slots_read`` and ``lm.attn_slots_ring``, read
back with the tokens too.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.core import current as _obs_current, span as _obs_span
from repro.serving import kvcache
from repro.serving.engine import ItemRequest

DEFAULT_CACHE_LEN = 128


@dataclasses.dataclass
class LMRequest(ItemRequest):
    """An :class:`ItemRequest` that carries a token prompt. ``items``
    is a ``(max_new_tokens, 1)`` placeholder — row count = tokens to
    generate; the streamed "outputs" are the generated token ids."""
    prompt: Tuple[int, ...] = ()


def lm_request(prompt, max_new_tokens: int = 16, *, uid: int = 0,
               key=None) -> LMRequest:
    """Build an LM decode request (the router stamps ``uid``/``key``
    on submission paths that own them)."""
    prompt = tuple(int(t) for t in prompt)
    if not prompt:
        raise ValueError("lm_request: empty prompt")
    if max_new_tokens < 1:
        raise ValueError("lm_request: max_new_tokens must be >= 1")
    return LMRequest(uid=uid,
                     items=np.zeros((int(max_new_tokens), 1),
                                    np.float32),
                     key=key, prompt=prompt)


def tokens_from_state(st) -> List[int]:
    """Generated token ids of a (finished or in-flight) lane state."""
    return [int(round(float(o[0]))) for o in st.outputs]


class LMMember:
    """One LM tenant on the shared multi-app router.

    Quacks like a fleet member (``d_in``/``stream_host``/``n_chips``)
    plus the admit/release hooks :class:`repro.deploy.MultiAppRouter`
    drives; deliberately does NOT expose ``.chip`` — the analytic cost
    compile lives on ``clm.chip``, and the router's "analytic-only
    tenants cannot stream" check must not mistake this member for one.
    Decode runs as one batched host-graph jit over all lanes
    (single-process; the fabric-side economics are the programmed tile
    plans inside ``clm``).
    """

    d_in = 1                    # one token id per streamed item
    is_lm = True
    is_distributed = False

    def __init__(self, clm, *, lanes: int,
                 cache_len: int = DEFAULT_CACHE_LEN, n_chips: int = 1):
        if lanes < 1:
            raise ValueError("LMMember: needs lanes >= 1")
        if cache_len < 2:
            raise ValueError("LMMember: cache_len must be >= 2")
        self.clm = clm
        self.cfg = clm.cfg
        self.cache_len = int(cache_len)
        self.lanes = int(lanes)
        self.n_chips = int(n_chips)
        self.n_local_chips = int(n_chips)
        self.prefill_tokens = 0
        self.decode_steps = 0
        self.last_logits = None     # (lanes, vocab) on the device
        self.last_live = 0          # lanes the last step decoded for
        # MoE dispatch counts summed over steps: assignments, rows,
        # dropped (zeros for a dense model)
        self.expert_counts = np.zeros((3,), np.int64)
        self._alloc(self.lanes)

    def _alloc(self, lanes: int) -> None:
        # bfloat16 unless the config asks for a float32 cache (int8
        # caches are laid out by the config itself)
        dtype = jnp.float32 if self.cfg.kv_cache_dtype == "float32" \
            else jnp.bfloat16
        self.cache = self.clm.init_cache(lanes, self.cache_len, dtype)
        self._next_tok = np.zeros((lanes,), np.int32)
        self._pos = np.zeros((lanes,), np.int32)
        self._live: set = set()

    # ---------------- lane lifecycle hooks -------------------------- #
    def on_admit(self, lane: int, st) -> None:
        """Fresh admission AND re-admission after eviction: prefill
        prompt + already-emitted tokens, write the lane's KV slot,
        stage the next greedy token."""
        req = st.request
        prompt = tuple(getattr(req, "prompt", ()) or ())
        if not prompt:
            raise ValueError(
                f"request {req.uid}: an LM lane needs a token prompt — "
                "build requests with repro.lm.lm_request (or "
                "Deployment.submit_tokens)")
        context = list(prompt) + tokens_from_state(st)
        if len(context) > self.cache_len:
            # ring-cache resume: only the last cache_len tokens fit the
            # lane; positions restart, so this is the documented lossy
            # fallback (CI sizes cache_len >= prompt + max_new_tokens)
            context = context[-self.cache_len:]
        with _obs_span("lm.prefill", cat="lm"):
            logits, one_cache = self.clm.prefill(
                jnp.asarray(context, jnp.int32)[None, :])
            self.cache = kvcache.write_slot(self.cache, one_cache,
                                            jnp.int32(lane))
            self._next_tok[lane] = int(jnp.argmax(logits[0]))
        self._pos[lane] = len(context)
        self._live.add(lane)
        self.prefill_tokens += len(context)
        tel = _obs_current()
        if tel.active:
            tel.metrics.counter("lm.prefill_tokens").inc(len(context))

    def on_release(self, lane: int) -> None:
        self.cache = kvcache.clear_slot(self.cache, jnp.int32(lane))
        self._live.discard(lane)
        # a free lane decodes junk at its position: at 0 it does not
        # hold the latent decode's prefix at the ring's length
        self._pos[lane] = 0

    # ---------------- one batched decode step ----------------------- #
    def stream_host(self, batch: np.ndarray, *,
                    use_kernel: bool = False) -> np.ndarray:
        """(lanes, 1) placeholder in → (lanes, 1) token ids out: emit
        each lane's staged token, then one batched decode (every lane
        at its own position) stages the next."""
        out = self._next_tok.astype(np.float32)[:, None]
        t0 = time.perf_counter()
        with _obs_span("lm.decode", cat="lm"):
            nxt, self.last_logits, counts, read, self.cache = \
                self.clm.serve_step(self.cache, self._next_tok[:, None],
                                    self._pos, use_kernel=use_kernel)
            nxt, counts, read = jax.device_get((nxt, counts, read))
        dt = time.perf_counter() - t0
        for lane in self._live:
            self._next_tok[lane] = nxt[lane]
            self._pos[lane] += 1
        self.decode_steps += 1
        self.expert_counts += counts
        live = self.last_live = len(self._live)
        tel = _obs_current()
        if tel.active and live:
            m = tel.metrics
            m.counter("lm.tokens").inc(live)
            m.histogram("lm.decode_latency_s").record(dt / live)
            if counts[1]:
                m.counter("lm.expert_assignments").inc(int(counts[0]))
                m.counter("lm.expert_rows").inc(int(counts[1]))
                m.counter("lm.expert_dropped").inc(int(counts[2]))
            if read[1]:
                m.counter("lm.attn_slots_read").inc(int(read[0]))
                m.counter("lm.attn_slots_ring").inc(int(read[1]))
        return out

    # ---------------- elastic resize -------------------------------- #
    def resize(self, *, lanes: int, mesh=None) -> None:
        """Rebuild the lane-batched KV cache for a new lane budget.
        Call BEFORE the router requeues evicted lanes — their states
        re-admit through :meth:`on_admit`, which re-prefills into the
        fresh cache (greedy determinism preserves the continuations)."""
        if lanes < 1:
            raise ValueError("LMMember.resize: needs lanes >= 1")
        self.lanes = int(lanes)
        self._alloc(self.lanes)
