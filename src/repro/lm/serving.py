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
  step    → dispatch ONE batched ``CompiledLM.serve_step`` over every
            lane at its own position, on the previous step's next
            tokens still on the device (a lane admitted since decodes
            its staged token, merged in the same jitted step); then
            read the previous step's tokens back and emit them. One
            step stays in flight, so the router's bookkeeping and the
            next dispatch run while the device computes (inactive lanes
            decode junk that ring-position masking ignores and the next
            admit overwrites — same discipline as ``serving.Engine``)
  release → ``kvcache.clear_slot``; the lane's token from the step in
            flight is dropped

Re-admission after an eviction (elastic resize / requeue) re-prefills
prompt + already-emitted tokens: greedy decoding is deterministic, so
the continuation picks up exactly where the evicted lane stopped, and
nothing is re-emitted (the scheduler's ``pos`` survives the trip).

Token telemetry rides the ``repro.obs`` registry: ``lm.tokens``
(counter, one per live lane per step), ``lm.prefill_tokens`` and a
per-token ``lm.decode_latency_s`` histogram. Spans: ``lm.prefill`` (one
admission: prefill, slot write, first token on the host) and
``lm.decode`` (one call: the dispatch of this call's step and the wait
for the previous step's tokens, which the call returns — the host's
wait per step, not one step from dispatch to its own tokens). Counters
``lm.decode_dispatched`` (every step dispatched) and
``lm.decode_overlapped`` (the steps dispatched while the previous step
was still running; below every step, the host sets the pace). A MoE
tenant's dispatch counts come back with the step's tokens in the same
read-back, never by a sync of their own:
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
        # a lane admitted since the last dispatch: its first token, on
        # the host; every other live lane's is the step in flight's
        self._staged = np.zeros((lanes,), np.int32)
        self._fresh = np.zeros((lanes,), bool)
        self._pos = np.zeros((lanes,), np.int32)
        self._live: set = set()
        # the step in flight: (next tokens, MoE counts, attention read)
        # on the device, and the lanes it decoded for
        self._flight = None

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
            self._staged[lane] = int(jnp.argmax(logits[0]))
        self._fresh[lane] = True
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
        if not self._live and self._flight is not None:
            self._collect()         # no lane waits on it: count it now

    # ---------------- one batched decode step ----------------------- #
    def stream_host(self, batch: np.ndarray, *,
                    use_kernel: bool = False) -> np.ndarray:
        """(lanes, 1) placeholder in → (lanes, 1) token ids out, with
        one decode step kept in flight: dispatch this call's step (every
        lane at its own position) on the previous step's next tokens,
        still on the device, or a freshly admitted lane's staged token;
        then read the previous step's tokens back and return them with
        the staged ones — this step's inputs, which is what the lanes
        emit. Host arrays the step reads are copies, so advancing the
        positions after dispatch never reaches the step in flight."""
        flight = self._flight
        staged, fresh = self._staged.copy(), self._fresh.copy()
        live = self.last_live = len(self._live)
        t0 = time.perf_counter()
        with _obs_span("lm.decode", cat="lm"):
            # nothing in flight: every live lane is fresh
            prev = staged if flight is None else flight[0]
            overlapped = flight is not None and not prev.is_ready()
            nxt, self.last_logits, counts, read, self.cache = \
                self.clm.serve_step(self.cache, staged[:, None],
                                    self._pos.copy(), prev=prev,
                                    fresh=fresh, use_kernel=use_kernel)
            self._fresh[:] = False
            for lane in self._live:
                self._pos[lane] += 1
            out = staged if flight is None else \
                np.where(fresh, staged, self._collect())
            self._flight = (nxt, counts, read, live)
        dt = time.perf_counter() - t0
        self.decode_steps += 1
        tel = _obs_current()
        if tel.active:
            m = tel.metrics
            m.counter("lm.decode_dispatched").inc()
            m.counter("lm.decode_overlapped").inc(int(overlapped))
            if live:
                m.counter("lm.tokens").inc(live)
                m.histogram("lm.decode_latency_s").record(dt / live)
        return out.astype(np.float32)[:, None]

    def _collect(self) -> np.ndarray:
        """Read the step in flight back in one transfer: count its MoE
        dispatch and attention read, and return its next tokens."""
        nxt, counts, read, live = self._flight
        self._flight = None
        nxt, counts, read = jax.device_get((nxt, counts, read))
        self.expert_counts += counts
        tel = _obs_current()
        if tel.active and live:
            m = tel.metrics
            if counts[1]:
                m.counter("lm.expert_assignments").inc(int(counts[0]))
                m.counter("lm.expert_rows").inc(int(counts[1]))
                m.counter("lm.expert_dropped").inc(int(counts[2]))
            if read[1]:
                m.counter("lm.attn_slots_read").inc(int(read[0]))
                m.counter("lm.attn_slots_ring").inc(int(read[1]))
        return nxt

    # ---------------- elastic resize -------------------------------- #
    def resize(self, *, lanes: int, mesh=None) -> None:
        """Rebuild the lane-batched KV cache for a new lane budget.
        Call BEFORE the router requeues evicted lanes — their states
        re-admit through :meth:`on_admit`, which re-prefills into the
        fresh cache (greedy determinism preserves the continuations).
        The step in flight is counted and its tokens dropped: the
        re-prefill of prompt + emitted tokens stages the same ones."""
        if lanes < 1:
            raise ValueError("LMMember.resize: needs lanes >= 1")
        if self._flight is not None:
            self._collect()         # its tokens re-prefill, dropped here
        self.lanes = int(lanes)
        self._alloc(self.lanes)
