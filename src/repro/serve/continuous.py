"""Continuous-batching LM decode plane: slot-scheduled serving over a
paged KV cache.

The bucketed ``MicroBatcher`` path is batch-synchronous: a request that
finishes generating early stalls its bucket, and new arrivals wait for
the next one.  This module admits and retires requests independently —
the serving shape the client-side surveys (arXiv:1909.08329,
arXiv:1909.08364) identify as where production inference throughput
comes from:

* ``DecodeScheduler`` owns the host-side control plane: ``n_slots``
  decode slots, a ``PageAllocator`` over one shared ``PagedKVCache``
  arena, the slot → page **block table**, and a FIFO backlog for
  requests the arena cannot place yet.
* ``ContinuousLMEngine`` owns the data plane: ONE compiled step advances
  every slot one token against the persistent paged cache (donated on
  accelerators).  The block table, per-slot lengths and sampling seeds
  are jit *arguments* — host numpy of static shape — so joins, leaves
  and evictions are pure data changes: **the compiled step never
  retraces** (asserted via ``compiled_step_cache_size`` and
  ``program_cache_stats()``).  Joins prefill the prompt through the
  dense B=1 decode path (power-of-two prompt buckets) and scatter the
  result into the slot's pages; leaves just free the pages — freed rows
  point at the null page, so in-flight garbage writes stay invisible.

Attention on the hot path runs through ``kernels/decode_attention``
(``use_kernel="auto"``: the paged Pallas kernel on TPU, which reads each
slot's live pages through the block table; elsewhere the jitted XLA
reference over the gathered page view), with the choice reported in
``kernel_plan`` and per-token hit counts in ``kernel_hits`` — the
serve-side analogue of ``wire_kernel_hits``.

Requests resolve through the same ``Ticket`` handle the batcher uses; an
evicted or errored request **fails its ticket immediately** instead of
hanging until timeout.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.executor import cached_program
from repro.models import transformer as tf
from repro.models.attention import decode_kernel_plan, resolve_decode_attn
from repro.models.cache import NULL_PAGE, PageAllocator
from repro.models.config import ModelConfig
from repro.serve.batcher import Ticket
from repro.serve.metrics import ServeMetrics
from repro.telemetry import trace as _trace


class EvictedError(RuntimeError):
    """Raised from ``Ticket.result()`` when the request was evicted
    mid-generation (admin action or slot reclaim) rather than completed."""


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray  # (P,) int32
    max_new: int
    ticket: Ticket
    t_submit: float
    seed: int
    slot: int = -1
    pages: list = field(default_factory=list)
    tokens: list = field(default_factory=list)


class DecodeScheduler:
    """Host-side control plane: slots, pages, backlog.

    Admission is all-or-nothing: a request needs a free slot AND enough
    pages for its whole lifetime (``ceil((prompt + max_new) / page_size)``
    — known up front, so a placed request can never run out of pages
    mid-generation).  When either is missing the request waits in the
    FIFO backlog; it is admitted the moment a retiring request frees
    capacity.  Long and short requests draw from the same arena, so
    ``n_pages`` can be provisioned well below
    ``n_slots × pages_per_slot``.
    """

    def __init__(self, *, n_slots: int, n_pages: int, page_size: int,
                 max_seq: int):
        if max_seq < 1:
            raise ValueError(f"max_seq={max_seq}")
        self.n_slots = n_slots
        self.page_size = page_size
        self.max_seq = max_seq
        self.pages_per_slot = -(-max_seq // page_size)
        self.alloc = PageAllocator(n_pages)
        self.block = np.full(
            (n_slots, self.pages_per_slot), NULL_PAGE, np.int32
        )
        self.length = np.zeros((n_slots,), np.int32)
        self.slots: list = [None] * n_slots
        self.backlog: deque = deque()

    # -- capacity ------------------------------------------------------------

    def pages_needed(self, req: _Request) -> int:
        return -(-(len(req.prompt) + req.max_new) // self.page_size)

    def check_fits(self, req: _Request) -> None:
        """Raise if ``req`` could never be placed, even on an idle arena."""
        total = len(req.prompt) + req.max_new
        if total > self.max_seq:
            raise ValueError(
                f"request needs {total} positions > max_seq={self.max_seq}"
            )
        if self.pages_needed(req) > self.alloc.n_pages - 1:
            raise ValueError(
                f"request needs {self.pages_needed(req)} pages but the "
                f"arena only has {self.alloc.n_pages - 1} allocatable"
            )

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)

    # -- join / leave --------------------------------------------------------

    def admit(self, req: _Request) -> int | None:
        """Place ``req`` in a free slot with pages reserved, or return
        None (caller keeps it in the backlog)."""
        slot = next(
            (s for s, r in enumerate(self.slots) if r is None), None
        )
        if slot is None:
            return None
        pages = self.alloc.alloc(self.pages_needed(req))
        if pages is None:
            return None
        self.slots[slot] = req
        req.slot = slot
        req.pages = pages
        self.block[slot, :] = NULL_PAGE
        self.block[slot, : len(pages)] = pages
        self.length[slot] = 0
        return slot

    def release(self, slot: int) -> _Request:
        """Free a slot's pages and point its block row back at the null
        page (the compiled step keeps 'writing' for this slot — into
        trash memory no live sequence can see)."""
        req = self.slots[slot]
        if req is None:
            raise ValueError(f"release of empty slot {slot}")
        self.alloc.free(req.pages)
        req.pages = []
        req.slot = -1
        self.slots[slot] = None
        self.block[slot, :] = NULL_PAGE
        self.length[slot] = 0
        return req


def _build_step(cfg: ModelConfig, impl: str, temperature: float,
                donate: bool):
    """The ONE compiled program of the decode plane: advance every slot a
    token and sample the next on device (no (n_slots, V) transfer).  For
    a stack whose expert layers hold a share of the experts it returns a
    third output, (n_slots,) int32: each slot's routed (token, expert)
    pairs that landed on held experts, over the expert layers.

    Sampling keys are ``fold_in(key(seed), position)`` — a pure function
    of per-request data, so a request's sampled tokens are invariant to
    which slot it landed in and who else is in flight.
    """

    def step(params, tokens, cache, block, length, seeds):
        logits, new_cache, *held = tf.paged_decode_step(
            params, cfg, tokens, cache, block, length, decode_attn=impl,
        )
        lg = logits[:, 0, : cfg.vocab_size]
        if temperature > 0:
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.key(s), p)
            )(seeds, length)
            nxt = jax.vmap(jax.random.categorical)(keys, lg / temperature)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        return (nxt.astype(jnp.int32), new_cache, *held)

    return jax.jit(step, donate_argnames=("cache",) if donate else ())


class ContinuousLMEngine:
    """Slot-scheduled LM serving over a paged KV cache.

    Args:
      cfg / params: an attention or MLA LM, dense or with expert layers
        (``init_paged_cache`` rejects recurrent stacks), and its
        parameters.  MLA layers decode through a paged latent arena
        (``PagedMLACache``, the paged MLA kernel on TPU); expert layers
        that hold a share of the experts count each step's routes to
        them (``serve/moe_routes_held`` of ``serve/moe_routes``).
      n_slots: in-flight sequences the compiled step advances together.
      page_size: tokens per physical KV page.
      max_seq: longest prompt+generation a request may need (sets the
        block-table width).
      n_pages: arena capacity; default fully provisions
        ``n_slots × max_seq`` (+ the null page).  Smaller values
        oversubscribe — admission control queues what doesn't fit.
      use_kernel: decode-attention path — True forces the paged Pallas
        kernel, False the jitted XLA reference, "auto" picks by backend;
        the decision is reported in ``kernel_plan``.
      temperature / seed: sampling knobs (0 → greedy argmax).
      metrics / tracer / tag: same observability surfaces as
        ``ServeEngine`` (``RunReport.from_serve`` accepts either).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 8,
        page_size: int = 16,
        max_seq: int = 256,
        n_pages: int | None = None,
        use_kernel="auto",
        temperature: float = 0.0,
        seed: int = 0,
        metrics: ServeMetrics | None = None,
        tracer=None,
        tag: str = "serve/continuous",
    ):
        self.cfg = cfg
        self.params = params
        self.tag = tag
        self.temperature = float(temperature)
        self.seed = seed
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.tracer = tracer if tracer is not None else _trace.current_tracer()
        self.kernel_plan = decode_kernel_plan(cfg, use_kernel=use_kernel,
                                              paged=True)
        self._impl = resolve_decode_attn(
            use_kernel, sliding_window=cfg.sliding_window
        )
        #: tokens advanced through each decode-attention implementation —
        #: the serve-side analogue of ``wire_kernel_hits``
        self.kernel_hits = {"pallas": 0, "xla": 0}

        pages_per_slot = -(-max_seq // page_size)
        if n_pages is None:
            n_pages = 1 + n_slots * pages_per_slot
        self.sched = DecodeScheduler(
            n_slots=n_slots, n_pages=n_pages, page_size=page_size,
            max_seq=max_seq,
        )
        self._cache = tf.init_paged_cache(
            cfg, n_pages, page_size, jnp.dtype(cfg.compute_dtype)
        )
        self._last_tok = np.zeros((n_slots,), np.int32)
        self._seeds = np.zeros((n_slots,), np.int32)
        self._rid = 0
        self._lock = threading.RLock()
        #: routed (token, expert) pairs a decoded token makes, over the
        #: expert layers
        self._routes_per_token = cfg.moe.top_k * sum(
            s.ffn == "moe" for s in tf.layer_specs(cfg)) if cfg.holds_expert_share else 0

        donate = jax.default_backend() != "cpu"
        self._step = cached_program(
            ("serve/continuous_step", cfg, n_slots, page_size,
             pages_per_slot, n_pages, self._impl, self.temperature, donate),
            lambda: _build_step(cfg, self._impl, self.temperature, donate),
        )
        self._prefill = cached_program(
            ("serve/continuous_prefill", cfg),
            lambda: jax.jit(
                lambda p, t, c, pos: tf.decode_step(
                    p, cfg, t, c, positions=pos
                )
            ),
        )
        self._insert = cached_program(
            ("serve/continuous_insert", cfg, n_pages, page_size),
            lambda: jax.jit(
                tf.paged_insert_prompt,
                donate_argnames=("paged",) if donate else (),
            ),
        )

    # -- introspection -------------------------------------------------------

    @property
    def compiled_step_cache_size(self) -> int:
        """Distinct traces of the compiled decode step — stays 1 under
        arbitrary join/leave churn (the no-retrace contract)."""
        return self._step._cache_size()

    @property
    def ledger(self):
        return self.metrics.ledger

    def stats(self) -> dict:
        out = self.metrics.summary()
        out["slots"] = self.sched.n_slots
        out["backlog"] = len(self.sched.backlog)
        return out

    # -- request lifecycle ---------------------------------------------------

    def submit(self, prompt, *, max_new: int) -> Ticket:
        """Queue one generation request; returns a ``Ticket`` whose
        ``result()`` is the (max_new,) int32 generated ids."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new={max_new}")
        with self._lock:
            rid = self._rid
            self._rid += 1
            req = _Request(
                rid=rid, prompt=prompt, max_new=max_new,
                ticket=Ticket(self, rid), t_submit=time.perf_counter(),
                seed=(self.seed * 1_000_003 + rid) & 0x7FFFFFFF,
            )
            self.sched.check_fits(req)  # reject the never-servable loudly
            self.sched.backlog.append(req)
        return req.ticket

    def evict(self, ticket: Ticket, reason: str = "evicted") -> None:
        """Drop a request (in flight or queued) and fail its ticket with
        ``EvictedError`` immediately — it never hangs until timeout."""
        with self._lock:
            rid = ticket._key
            req = next(
                (r for r in self.sched.slots if r is not None and r.rid == rid),
                None,
            )
            if req is not None:
                self.sched.release(req.slot)
            else:
                req = next(
                    (r for r in self.sched.backlog if r.rid == rid), None
                )
                if req is None:
                    return  # already resolved
                self.sched.backlog.remove(req)
            self.metrics.record_eviction()
            tr = self.tracer
            if tr is not None:
                tr.count("serve/evictions")
            req.ticket._fail(
                EvictedError(f"request {rid} {reason} after "
                             f"{len(req.tokens)}/{req.max_new} tokens")
            )

    # -- the decode loop -----------------------------------------------------

    def _admit_from_backlog(self) -> int:
        """Join as many queued requests as the arena can place (FIFO — a
        stuck head request must not be starved by smaller later ones)."""
        joined = 0
        while self.sched.backlog:
            req = self.sched.backlog[0]
            slot = self.sched.admit(req)
            if slot is None:
                break
            self.sched.backlog.popleft()
            self._join(req, slot)
            joined += 1
        return joined

    def _span(self, name: str, **tags):
        """A span on the engine's tracer; a null context without one."""
        tr = self.tracer
        return nullcontext() if tr is None else tr.span(name, **tags)

    def _join(self, req: _Request, slot: int) -> None:
        """Prefill the prompt (dense B=1 path, power-of-two bucket) and
        scatter the result into the slot's pages; the first generated
        token comes from the prefill logits."""
        P = len(req.prompt)
        bucket = 1 << max(0, (P - 1).bit_length())
        tr = self.tracer
        with self._span("serve/prefill", rid=req.rid, prompt=P, bucket=bucket,
                        slot=slot):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :P] = req.prompt
            dense = tf.init_cache(
                self.cfg, 1, bucket, jnp.dtype(self.cfg.compute_dtype)
            )
            pos = jnp.broadcast_to(jnp.arange(bucket), (1, bucket))
            logits, dense = self._prefill(
                self.params, jnp.asarray(toks), dense, pos
            )
            self._cache = self._insert(
                self._cache, dense, jnp.asarray(self.sched.block[slot]),
                jnp.asarray(np.int32(P)),
            )
            if tr is not None:
                # the span covers the device's prefill and insert; the
                # first-token sample below waits for them anyway
                jax.block_until_ready(self._cache)
        first = self._sample_host(logits[0, P - 1], req.seed, P - 1)
        req.tokens.append(int(first))
        self.sched.length[slot] = P
        self._last_tok[slot] = first
        self._seeds[slot] = req.seed
        if tr is not None:
            tr.count("serve/joins")
        self._retire_if_done(slot)

    def _sample_host(self, logits_row, seed: int, position: int) -> int:
        """Same sampling math as the compiled step, for the one token that
        comes from prefill logits (key is (seed, position) — slot- and
        occupancy-invariant)."""
        lg = logits_row[: self.cfg.vocab_size]
        if self.temperature > 0:
            key = jax.random.fold_in(jax.random.key(seed), position)
            return int(jax.random.categorical(key, lg / self.temperature))
        return int(jnp.argmax(lg))

    def _retire_if_done(self, slot: int) -> None:
        req = self.sched.slots[slot]
        if req is None or len(req.tokens) < req.max_new:
            return
        self.sched.release(slot)
        e2e = time.perf_counter() - req.t_submit
        out = np.asarray(req.tokens, np.int32)
        self.metrics.record_request_stream(
            len(req.tokens), e2e, request=req.prompt, response=out,
            tag=self.tag,
        )
        tr = self.tracer
        if tr is not None:
            tr.count("serve/requests")
        req.ticket._resolve(out)

    def step(self) -> int:
        """One scheduler tick: admit what fits, advance every slot one
        token, retire finished requests.  Returns tokens produced."""
        with self._lock, self._span("serve/step"):
            self._admit_from_backlog()
            active = [s for s, r in enumerate(self.sched.slots) if r is not None]
            if not active:
                return 0
            n_slots = self.sched.n_slots
            P = self.sched.page_size
            t0 = time.perf_counter()
            try:
                # pages the paged kernel reads (each slot's live pages,
                # the new token's included) against the block table's
                with self._span(
                    "serve/decode_step", active=len(active), slots=n_slots,
                    kv_pages_read=int(np.sum((self.sched.length + P) // P)),
                    kv_pages_table=self.sched.block.size,
                ):
                    with self._span("serve/decode_step.inputs"):
                        nxt, self._cache, *held = self._step(
                            self.params,
                            jnp.asarray(self._last_tok[:, None]),
                            self._cache,
                            jnp.asarray(self.sched.block),
                            jnp.asarray(self.sched.length),
                            jnp.asarray(self._seeds),
                        )
                    with self._span("serve/decode_step.wait"):
                        nxt, *held = jax.block_until_ready((nxt, *held))
                        nxt = np.asarray(nxt)
            except BaseException as e:
                # fail every in-flight ticket NOW — a dead decode loop
                # must not leave callers hanging until their timeout
                for s in list(active):
                    req = self.sched.release(s)
                    req.ticket._fail(e)
                raise
            dt = time.perf_counter() - t0
            with self._span("serve/emit"):
                self.metrics.record_decode_step(len(active), n_slots, dt)
                self.kernel_hits[self._impl] += len(active)
                if self.tracer is not None:
                    self.tracer.count("serve/decode_tokens", len(active))
                    if held:
                        self.tracer.count("serve/moe_routes_held",
                                          int(np.asarray(held[0])[active].sum()))
                        self.tracer.count("serve/moe_routes",
                                          len(active) * self._routes_per_token)
                for s in active:
                    req = self.sched.slots[s]
                    req.tokens.append(int(nxt[s]))
                    self.sched.length[s] += 1
                    self._last_tok[s] = nxt[s]
                    self._retire_if_done(s)
            return len(active)

    def flush(self, key=None) -> int:
        """Drive the loop until request ``key`` resolves (None → until
        idle).  This is the ``Ticket.result()`` hook — the same owner
        protocol the ``MicroBatcher`` implements."""
        served = 0
        while True:
            with self._lock:
                if key is not None:
                    req = self._find(key)
                    if req is None or req.ticket.done:
                        return served
                elif not (self.sched.backlog or self.sched.n_active):
                    return served
            if self.step() == 0:
                with self._lock:
                    if self.sched.backlog and not self.sched.n_active:
                        # nothing in flight frees capacity — unreachable
                        # for requests that passed check_fits, but guard
                        # against a wedged loop anyway
                        raise RuntimeError(
                            "backlog cannot be placed on an idle arena"
                        )
            else:
                served += 1

    def _find(self, rid: int) -> _Request | None:
        # resolved/evicted requests are in neither structure — their
        # tickets already hold the value/error, so flush has no work
        for r in self.sched.slots:
            if r is not None and r.rid == rid:
                return r
        for r in self.sched.backlog:
            if r.rid == rid:
                return r
        return None

    def run_until_idle(self) -> int:
        """Serve everything queued; returns decode steps taken."""
        return self.flush()
