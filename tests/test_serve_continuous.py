"""Continuous-batching decode plane: paged cache, scheduler, equivalence.

The contracts this file pins down:

* ``PageAllocator`` — all-or-nothing allocation, loud double-free, LIFO
  reuse, page 0 never handed out.
* paged cache ops — write/append/view round-trip exactly; null-page
  redirection keeps inactive slots invisible.
* decode-attention hot path — the dense Pallas kernel (interpret on
  CPU, single KV block) is BITWISE equal to the jitted XLA reference,
  through ``attn_apply`` and standalone; the paged kernel serves the
  same greedy tokens as the XLA path over the gathered page view.
* continuous ≡ one-at-a-time ≡ dense-baseline decode (greedy ids — the
  slot scheduler may not change a single served token).
* retrace freedom — the ONE compiled step's jit cache stays at size 1
  under arbitrary join/leave/evict churn (block table and lengths are
  data, not shapes).
* failure semantics — eviction and decode errors fail tickets
  immediately; a poisoned batcher group cannot hang other groups.
* 8-fake-device subprocess acceptance run.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.executor import clear_program_cache, program_cache_stats
from repro.models import cache as cache_lib
from repro.models import transformer as tf
from repro.models.attention import attn_apply, decode_kernel_plan
from repro.models.cache import NULL_PAGE, PageAllocator
from repro.models.config import ModelConfig
from repro.serve import ContinuousLMEngine, DecodeScheduler, EvictedError
from repro.telemetry.report import RunReport
from repro.telemetry.trace import Tracer


def _tiny_cfg(**kw):
    base = dict(
        name="tiny", vocab_size=97, d_model=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
        compute_dtype="float32", param_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = _tiny_cfg()
    params = tf.init_params(jax.random.key(0), cfg)
    return cfg, params


# ----------------------------------------------------------------------------
# PageAllocator invariants
# ----------------------------------------------------------------------------


class TestPageAllocator:
    def test_never_hands_out_null_page_and_reuses_freed(self):
        a = PageAllocator(8)
        seen = set()
        first = a.alloc(7)
        assert first is not None and NULL_PAGE not in first
        seen.update(first)
        assert a.free_pages == 0
        a.free(first)
        second = a.alloc(7)
        assert set(second) == seen  # full reuse of the same physical pool

    def test_all_or_nothing(self):
        a = PageAllocator(5)  # 4 allocatable
        assert a.alloc(5) is None
        assert a.free_pages == 4  # a refused alloc takes nothing
        got = a.alloc(4)
        assert len(got) == 4
        assert a.alloc(1) is None

    def test_double_free_and_foreign_free_raise(self):
        a = PageAllocator(4)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(ValueError, match="double free|not allocated"):
            a.free(pages)
        with pytest.raises(ValueError, match="not allocated"):
            a.free([NULL_PAGE])

    def test_lifo_reuse(self):
        a = PageAllocator(8)
        x = a.alloc(3)
        a.free(x)
        y = a.alloc(3)
        assert y == list(reversed(x))  # most recently freed comes back first

    def test_negative_and_tiny_arena_rejected(self):
        with pytest.raises(ValueError):
            PageAllocator(1)
        a = PageAllocator(4)
        with pytest.raises(ValueError):
            a.alloc(-1)


# ----------------------------------------------------------------------------
# Paged cache ops
# ----------------------------------------------------------------------------


class TestPagedCacheOps:
    def test_write_view_append_roundtrip(self):
        P, Hkv, D = 4, 2, 3
        cache = cache_lib.paged_kv_cache_init(7, P, Hkv, D, jnp.float32)
        block = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        rng = np.random.default_rng(0)
        k_seq = jnp.asarray(rng.normal(size=(8, Hkv, D)), jnp.float32)
        v_seq = jnp.asarray(rng.normal(size=(8, Hkv, D)), jnp.float32)
        # write 5 valid rows (3 rows of bucket padding) into slot 0
        cache = cache_lib.paged_write(cache, block[0], k_seq, v_seq, 5)
        k, v = cache_lib.paged_view(cache, block)
        np.testing.assert_array_equal(k[0, :5], k_seq[:5])
        np.testing.assert_array_equal(v[0, :5], v_seq[:5])
        # slot 1 untouched
        np.testing.assert_array_equal(k[1], np.zeros((12, Hkv, D)))

        # append one token per slot at its fill position
        k_tok = jnp.asarray(rng.normal(size=(2, Hkv, D)), jnp.float32)
        v_tok = jnp.asarray(rng.normal(size=(2, Hkv, D)), jnp.float32)
        cache = cache_lib.paged_append(
            cache, block, jnp.asarray([5, 0], jnp.int32), k_tok, v_tok
        )
        k, v = cache_lib.paged_view(cache, block)
        np.testing.assert_array_equal(k[0, 5], k_tok[0])
        np.testing.assert_array_equal(k[1, 0], k_tok[1])
        np.testing.assert_array_equal(k[0, :5], k_seq[:5])  # intact

    def test_arena_layout_matches_dense_reference(self):
        """Writes and appends land at ``arena[page, head, row]`` — the
        (n_pages, H_kv, page_size, D) layout the paged kernel copies —
        where a dense per-slot cache holds ``cache[slot, pos, head]``."""
        P, Hkv, D, pps = 4, 2, 3, 3
        rng = np.random.default_rng(4)
        cache = cache_lib.paged_kv_cache_init(1 + 2 * pps, P, Hkv, D, jnp.float32)
        assert cache.k.shape == (1 + 2 * pps, Hkv, P, D)
        block = np.asarray([[5, 2, 6], [1, 4, 3]], np.int32)
        dense_k = np.zeros((2, pps * P, Hkv, D), np.float32)
        dense_v = np.zeros_like(dense_k)
        fill = [6, 3]
        for s, n in enumerate(fill):
            kv = rng.normal(size=(2, 8, Hkv, D)).astype(np.float32)
            cache = cache_lib.paged_write(cache, jnp.asarray(block[s]),
                                          jnp.asarray(kv[0]), jnp.asarray(kv[1]), n)
            dense_k[s, :n], dense_v[s, :n] = kv[0, :n], kv[1, :n]
        length = np.asarray(fill, np.int32)
        for _ in range(4):  # crosses page boundaries in both slots
            tok = rng.normal(size=(2, 2, Hkv, D)).astype(np.float32)
            cache = cache_lib.paged_append(cache, jnp.asarray(block),
                                           jnp.asarray(length),
                                           jnp.asarray(tok[0]), jnp.asarray(tok[1]))
            for s in range(2):
                dense_k[s, length[s]], dense_v[s, length[s]] = tok[0, s], tok[1, s]
            length += 1
        k, v = np.asarray(cache.k), np.asarray(cache.v)
        for s in range(2):
            for pos in range(length[s]):
                page, row = block[s, pos // P], pos % P
                np.testing.assert_array_equal(k[page, :, row], dense_k[s, pos])
                np.testing.assert_array_equal(v[page, :, row], dense_v[s, pos])
        view_k, view_v = cache_lib.paged_view(cache, jnp.asarray(block))
        for s in range(2):
            np.testing.assert_array_equal(np.asarray(view_k[s, :length[s]]),
                                          dense_k[s, :length[s]])
            np.testing.assert_array_equal(np.asarray(view_v[s, :length[s]]),
                                          dense_v[s, :length[s]])

    def test_null_page_swallows_inactive_writes(self):
        P, Hkv, D = 2, 1, 2
        cache = cache_lib.paged_kv_cache_init(4, P, Hkv, D, jnp.float32)
        live = jnp.asarray([[1, 2]], jnp.int32)
        dead = jnp.full((1, 2), NULL_PAGE, jnp.int32)
        tok = jnp.ones((1, Hkv, D), jnp.float32)
        cache = cache_lib.paged_append(
            cache, dead, jnp.zeros((1,), jnp.int32), tok, tok
        )
        k, _ = cache_lib.paged_view(cache, live)
        np.testing.assert_array_equal(k, np.zeros_like(np.asarray(k)))

    def test_padding_rows_redirect_to_null_page(self):
        P, Hkv, D = 2, 1, 2
        cache = cache_lib.paged_kv_cache_init(4, P, Hkv, D, jnp.float32)
        block_row = jnp.asarray([1, 2], jnp.int32)
        seq = jnp.ones((4, Hkv, D), jnp.float32) * 7.0
        cache = cache_lib.paged_write(cache, block_row, seq, seq, 2)
        k, _ = cache_lib.paged_view(cache, block_row[None])
        np.testing.assert_array_equal(np.asarray(k[0, :2]), seq[:2])
        # rows >= n_valid landed in page 0, not pages 1/2
        np.testing.assert_array_equal(
            np.asarray(k[0, 2:]), np.zeros((2, Hkv, D))
        )


# ----------------------------------------------------------------------------
# Decode-attention hot path: kernel bit-equality
# ----------------------------------------------------------------------------


class TestDecodeKernelBitExact:
    def test_pallas_vs_xla_reference_bitwise(self):
        from repro.kernels.decode_attention import ops as da_ops

        rng = np.random.default_rng(1)
        for B, S, Hq, Hkv, D, vl in [
            (3, 64, 8, 2, 32, 17), (2, 32, 4, 4, 16, 32), (1, 16, 4, 1, 8, 1)
        ]:
            q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
            k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            valid = jnp.full((B,), vl, jnp.int32)
            got = da_ops.decode_attention(q, k, v, valid, bk=512)
            ref = da_ops.decode_attention_xla(q, k, v, valid)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_attn_apply_pallas_equals_xla(self, tiny_lm):
        cfg, params = tiny_lm
        p = params["seg0"]
        p0 = jax.tree.map(lambda x: x[0], p)["l0"]["mixer"]
        rng = np.random.default_rng(2)
        B, S = 2, 16
        x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
        cache = cache_lib.kv_cache_init(
            B, S, cfg.num_kv_heads, cfg.head_dim, jnp.float32
        )
        cache = cache._replace(index=jnp.asarray(5, jnp.int32))
        pos = jnp.full((B, 1), 5, jnp.int32)
        outs = {}
        for impl in ("pallas", "xla"):
            y, nc = attn_apply(
                p0, cfg, x, positions=pos, cache=cache, decode_attn=impl
            )
            outs[impl] = np.asarray(y)
        np.testing.assert_array_equal(outs["pallas"], outs["xla"])

    def test_kernel_plan_reports_fallback(self):
        plan = decode_kernel_plan(_tiny_cfg(), use_kernel="auto")
        assert plan["path"] in ("pallas", "xla")
        if jax.default_backend() != "tpu":
            assert plan["path"] == "xla"
            assert "bit-equal" in plan["reason"]
        forced = decode_kernel_plan(_tiny_cfg(), use_kernel=True)
        assert forced["path"] == "pallas"
        sw = decode_kernel_plan(_tiny_cfg(sliding_window=8))
        assert sw["path"] == "off"


# ----------------------------------------------------------------------------
# Scheduler
# ----------------------------------------------------------------------------


class TestDecodeScheduler:
    def test_admit_release_cycle(self):
        s = DecodeScheduler(n_slots=2, n_pages=9, page_size=4, max_seq=16)
        from repro.serve.continuous import _Request

        def req(rid, plen, gen):
            return _Request(
                rid=rid, prompt=np.zeros(plen, np.int32), max_new=gen,
                ticket=None, t_submit=0.0, seed=0,
            )

        r1, r2, r3 = req(1, 8, 8), req(2, 4, 4), req(3, 4, 4)
        assert s.admit(r1) is not None  # 4 pages
        assert s.admit(r2) is not None  # 2 pages
        assert s.n_active == 2
        assert s.admit(r3) is None  # no free slot
        s.release(r1.slot)
        assert (s.block[0] == NULL_PAGE).all() and s.length[0] == 0
        assert s.admit(r3) is not None
        assert s.alloc.used_pages == 4

    def test_oversubscribed_arena_queues_by_pages(self):
        # 2 slots but pages for only one 16-token request at a time
        s = DecodeScheduler(n_slots=2, n_pages=5, page_size=4, max_seq=16)
        from repro.serve.continuous import _Request

        a = _Request(rid=1, prompt=np.zeros(8, np.int32), max_new=8,
                     ticket=None, t_submit=0.0, seed=0)
        b = _Request(rid=2, prompt=np.zeros(8, np.int32), max_new=8,
                     ticket=None, t_submit=0.0, seed=0)
        assert s.admit(a) is not None
        assert s.admit(b) is None  # free slot exists, pages don't
        s.release(a.slot)
        assert s.admit(b) is not None

    def test_never_servable_rejected_at_submit(self, tiny_lm):
        cfg, params = tiny_lm
        eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                 max_seq=16)
        with pytest.raises(ValueError, match="max_seq"):
            eng.submit(np.zeros(12, np.int32), max_new=8)


# ----------------------------------------------------------------------------
# Equivalence: continuous ≡ one-at-a-time ≡ dense baseline
# ----------------------------------------------------------------------------


class TestContinuousEquivalence:
    PROMPTS = [(3, 6), (5, 3), (1, 5), (7, 2), (2, 4), (4, 6)]

    def _requests(self, cfg):
        rng = np.random.default_rng(0)
        return [
            (rng.integers(0, cfg.vocab_size, size=l).astype(np.int32), g)
            for l, g in self.PROMPTS
        ]

    def test_continuous_equals_one_at_a_time(self, tiny_lm):
        cfg, params = tiny_lm
        reqs = self._requests(cfg)
        eng = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                 max_seq=24)
        tickets = [eng.submit(p, max_new=g) for p, g in reqs]
        eng.run_until_idle()
        batched = [t.result().tolist() for t in tickets]

        solo = []
        for p, g in reqs:
            e1 = ContinuousLMEngine(cfg, params, n_slots=1, page_size=4,
                                    max_seq=24)
            solo.append(e1.submit(p, max_new=g).result().tolist())
        assert batched == solo

    def test_continuous_equals_dense_baseline(self, tiny_lm):
        from repro.launch.serve import prefill_and_decode

        cfg, params = tiny_lm
        reqs = self._requests(cfg)
        eng = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                 max_seq=24)
        tickets = [eng.submit(p, max_new=g) for p, g in reqs]
        eng.run_until_idle()
        for (p, g), t in zip(reqs, tickets):
            dense = prefill_and_decode(
                cfg, params, jnp.asarray(p)[None], gen=g,
                cache_len=len(p) + g + 1,
            )
            assert t.result().tolist() == np.asarray(dense)[0].tolist()

    def test_forced_pallas_kernel_on_hot_path(self, tiny_lm):
        """use_kernel=True routes the compiled step through the paged
        Pallas kernel (interpret on CPU) and counts hits — and the served
        ids are identical to the XLA-reference path."""
        cfg, params = tiny_lm
        reqs = self._requests(cfg)[:3]
        outs = {}
        for use_kernel in (True, False):
            eng = ContinuousLMEngine(
                cfg, params, n_slots=2, page_size=4, max_seq=24,
                use_kernel=use_kernel,
            )
            tickets = [eng.submit(p, max_new=g) for p, g in reqs]
            eng.run_until_idle()
            outs[use_kernel] = [t.result().tolist() for t in tickets]
            impl = "pallas" if use_kernel else "xla"
            assert eng.kernel_plan["path"] == impl
            assert eng.kernel_plan["kernel"] == (
                "paged_decode_attention" if use_kernel
                else "paged_view + decode_attention_xla")
            assert eng.kernel_hits[impl] > 0
            other = "xla" if use_kernel else "pallas"
            assert eng.kernel_hits[other] == 0
        assert outs[True] == outs[False]

    def test_paged_kernel_serves_xla_tokens_through_churn(self, tiny_lm):
        """The paged kernel and the XLA path serve the same greedy tokens
        through joins, retirements and page reuse: 9 requests over 3
        slots on an arena with pages for two full-length requests, so
        freed pages go to later requests in another order."""
        cfg, params = tiny_lm
        rng = np.random.default_rng(9)
        reqs = [(rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32),
                 int(g))
                for n, g in zip(rng.integers(1, 10, size=9),
                                rng.integers(2, 11, size=9))]
        outs, reused = {}, {}
        for use_kernel in (True, False):
            eng = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                     max_seq=20, n_pages=11,
                                     use_kernel=use_kernel)
            owner = {}  # page -> the last request that held it
            reused[use_kernel] = 0
            tickets = [eng.submit(p, max_new=g) for p, g in reqs]
            while eng.sched.backlog or eng.sched.n_active:
                eng.step()
                for r in eng.sched.slots:
                    if r is None or owner.get(r.pages[0]) == r.rid:
                        continue
                    reused[use_kernel] += any(p in owner for p in r.pages)
                    owner.update((p, r.rid) for p in r.pages)
            outs[use_kernel] = [t.result().tolist() for t in tickets]
            assert eng.compiled_step_cache_size == 1
        # joins that were placed on pages an earlier request had written
        assert reused[True] == reused[False] >= 4
        assert outs[True] == outs[False]

    def test_temperature_sampling_is_occupancy_invariant(self, tiny_lm):
        cfg, params = tiny_lm
        rng = np.random.default_rng(3)
        p0 = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)
        others = [
            rng.integers(0, cfg.vocab_size, size=l).astype(np.int32)
            for l in (2, 6)
        ]
        alone = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                   max_seq=16, temperature=0.7, seed=11)
        a = alone.submit(p0, max_new=5).result().tolist()
        crowd = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                   max_seq=16, temperature=0.7, seed=11)
        tickets = [crowd.submit(p0, max_new=5)]
        tickets += [crowd.submit(p, max_new=4) for p in others]
        crowd.run_until_idle()
        assert tickets[0].result().tolist() == a

    def test_under_provisioned_arena_still_serves_everything(self, tiny_lm):
        cfg, params = tiny_lm
        reqs = self._requests(cfg)
        # pages for ~1.5 requests at a time; 3 slots fight over them
        eng = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                 max_seq=24, n_pages=8)
        full = ContinuousLMEngine(cfg, params, n_slots=3, page_size=4,
                                  max_seq=24)
        t1 = [eng.submit(p, max_new=g) for p, g in reqs]
        t2 = [full.submit(p, max_new=g) for p, g in reqs]
        eng.run_until_idle()
        full.run_until_idle()
        assert [t.result().tolist() for t in t1] == \
               [t.result().tolist() for t in t2]
        assert eng.sched.alloc.used_pages == 0  # everything returned


# ----------------------------------------------------------------------------
# Retrace freedom
# ----------------------------------------------------------------------------


class TestRetraceFreedom:
    def test_compiled_step_never_retraces_under_churn(self, tiny_lm):
        cfg, params = tiny_lm
        clear_program_cache()
        eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                 max_seq=16)
        rng = np.random.default_rng(4)
        tickets = []
        # churn: staggered joins/leaves of mixed lengths + one eviction
        for i, (l, g) in enumerate([(3, 4), (1, 2), (5, 3), (2, 5), (4, 1)]):
            p = rng.integers(0, cfg.vocab_size, size=l).astype(np.int32)
            tickets.append(eng.submit(p, max_new=g))
            eng.step()
            assert eng.compiled_step_cache_size == 1, f"retrace at join {i}"
        eng.evict(tickets[-1])
        eng.run_until_idle()
        assert eng.compiled_step_cache_size == 1
        assert program_cache_stats()["misses"] >= 1  # step program is cached

    def test_program_cache_shares_step_across_engines(self, tiny_lm):
        cfg, params = tiny_lm
        clear_program_cache()
        ContinuousLMEngine(cfg, params, n_slots=2, page_size=4, max_seq=16)
        before = program_cache_stats()
        ContinuousLMEngine(cfg, params, n_slots=2, page_size=4, max_seq=16)
        after = program_cache_stats()
        assert after["hits"] >= before["hits"] + 3  # step+prefill+insert warm
        assert after["misses"] == before["misses"]


# ----------------------------------------------------------------------------
# Failure semantics
# ----------------------------------------------------------------------------


class TestFailureSemantics:
    def test_eviction_fails_ticket_immediately(self, tiny_lm):
        cfg, params = tiny_lm
        eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                 max_seq=16)
        keep = eng.submit(np.asarray([1, 2, 3], np.int32), max_new=4)
        drop = eng.submit(np.asarray([4, 5], np.int32), max_new=4)
        eng.step()  # both in flight
        eng.evict(drop, reason="test reclaim")
        with pytest.raises(EvictedError, match="test reclaim"):
            drop.result(timeout=0.1)  # fails NOW, not at timeout
        assert len(keep.result()) == 4  # survivor unaffected
        assert eng.stats()["evictions"] == 1

    def test_queued_request_eviction(self, tiny_lm):
        cfg, params = tiny_lm
        eng = ContinuousLMEngine(cfg, params, n_slots=1, page_size=4,
                                 max_seq=16)
        first = eng.submit(np.asarray([1, 2], np.int32), max_new=3)
        queued = eng.submit(np.asarray([3], np.int32), max_new=3)
        eng.step()  # first holds the only slot; second is backlogged
        eng.evict(queued)
        with pytest.raises(EvictedError):
            queued.result(timeout=0.1)
        assert len(first.result()) == 3

    def test_decode_error_fails_all_inflight_tickets(self, tiny_lm):
        cfg, params = tiny_lm
        eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                 max_seq=16)
        t1 = eng.submit(np.asarray([1, 2], np.int32), max_new=4)
        t2 = eng.submit(np.asarray([3], np.int32), max_new=4)
        eng.step()
        boom = RuntimeError("device fell over")
        eng._step = lambda *a, **k: (_ for _ in ()).throw(boom)
        with pytest.raises(RuntimeError, match="device fell over"):
            eng.step()
        for t in (t1, t2):
            with pytest.raises(RuntimeError, match="device fell over"):
                t.result(timeout=0.1)
        assert eng.sched.n_active == 0
        assert eng.sched.alloc.used_pages == 0  # pages reclaimed

    def test_batcher_poll_isolates_poisoned_group(self):
        from repro.serve import MicroBatcher

        calls = {"n": 0}

        def predict(X):
            if X.shape[1] == 2:  # the poisoned shape group
                raise ValueError("bad group")
            return X * 2

        b = MicroBatcher(predict, max_batch=4, timeout_s=0.0)
        bad = b.submit(np.ones(2, np.float32))
        good = b.submit(np.ones(3, np.float32))
        served = b.poll()  # must not raise, must serve the good group
        assert served >= 1
        np.testing.assert_array_equal(
            good.result(timeout=1), 2 * np.ones(3, np.float32)
        )
        with pytest.raises(ValueError, match="bad group"):
            bad.result(timeout=0.1)


# ----------------------------------------------------------------------------
# Metrics / report
# ----------------------------------------------------------------------------


class TestContinuousObservability:
    def test_metrics_and_report(self, tiny_lm):
        cfg, params = tiny_lm
        tr = Tracer()
        eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                 max_seq=16, tracer=tr)
        rng = np.random.default_rng(5)
        tickets = [
            eng.submit(rng.integers(0, cfg.vocab_size, size=3).astype(np.int32),
                       max_new=g)
            for g in (4, 2, 3)
        ]
        eng.run_until_idle()
        for t in tickets:
            t.result()
        s = eng.stats()
        assert s["requests"] == 3
        # the first token of each request comes from prefill logits;
        # ``tokens`` counts what the compiled decode step produced
        assert s["tokens"] == (4 - 1) + (2 - 1) + (3 - 1)
        assert s["tokens_per_s"] > 0
        assert 0 < s["slot_utilization"] <= 1
        assert s["decode_steps"] > 0
        assert s["p50_token_ms"] >= 0
        assert s["request_bytes"] == 3 * 3 * 4  # 3 prompts × 3 int32
        assert s["response_bytes"] == (4 + 2 + 3) * 4

        spans = tr.summary()
        assert "serve/decode_step" in spans and "serve/prefill" in spans
        assert tr.counters["serve/joins"] == 3
        assert tr.counters["serve/decode_tokens"] == s["tokens"]
        # mean slot occupancy: decoded tokens over slots stepped
        occupancy = tr.counters["serve/decode_tokens"] / (
            spans["serve/decode_step"]["count"] * 2)
        assert 0 < occupancy <= 1

        md = RunReport.from_serve(eng).to_markdown()
        assert "decode kernel hits" in md
        assert "tok/s" in md and "slot util" in md
        rep = RunReport.from_serve(eng).as_dict()
        assert rep["decode_kernel_hits"]["xla"] + \
               rep["decode_kernel_hits"]["pallas"] == s["tokens"]

    def test_traced_engine_span_tree_and_same_tokens(self, tiny_lm):
        cfg, params = tiny_lm
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (3, 5, 4)]

        def serve(tracer):
            eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                     max_seq=16, tracer=tracer)
            tickets = [eng.submit(p, max_new=g) for p, g in zip(prompts, (4, 2, 3))]
            eng.run_until_idle()
            return [np.asarray(t.result()) for t in tickets]

        tr = Tracer()
        traced, plain = serve(tr), serve(None)
        assert all(np.array_equal(a, b) for a, b in zip(traced, plain))
        by_id = {s["id"]: s for s in tr.spans}
        parent_of = {
            "serve/step": None, "serve/prefill": "serve/step",
            "serve/decode_step": "serve/step", "serve/emit": "serve/step",
            "serve/decode_step.inputs": "serve/decode_step",
            "serve/decode_step.wait": "serve/decode_step",
        }
        for s in tr.spans:
            up = by_id[s["parent"]]["name"] if s["parent"] is not None else None
            assert up == parent_of[s["name"]], s["name"]
        kids = {}
        for s in tr.spans:
            if s["parent"] is not None:
                kids.setdefault(s["parent"], []).append(s["name"])
        decode = [s for s in tr.spans if s["name"] == "serve/decode_step"]
        assert decode
        for s in decode:
            assert kids[s["id"]] == ["serve/decode_step.inputs", "serve/decode_step.wait"]
            assert "serve/emit" in kids[s["parent"]]
        prefill = [s for s in tr.spans if s["name"] == "serve/prefill"]
        assert sorted(s["tags"]["rid"] for s in prefill) == [0, 1, 2]

    def test_decode_step_names_its_device_work(self):
        """The compiled decode step's operations carry the token's append
        and the weight casts in their op metadata, and the page gather
        on the XLA path only: the paged kernel reads the arena through
        the block table."""
        from repro.serve import continuous

        cfg = _tiny_cfg(compute_dtype="bfloat16")
        params = tf.init_params(jax.random.key(0), cfg)
        n_slots, page, max_seq = 2, 4, 16
        pps = max_seq // page
        cache = tf.init_paged_cache(cfg, 1 + n_slots * pps, page, jnp.bfloat16)
        i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
        for impl in ("xla", "pallas"):
            step = continuous._build_step(cfg, impl, 0.0, False)
            text = step.lower(params, i32(n_slots, 1), cache, i32(n_slots, pps),
                              i32(n_slots), i32(n_slots)).compile().as_text()
            for scope in ("kv_append", "weight_cast"):
                assert f"/{scope}/" in text, (impl, scope)
            assert ("/kv_gather/" in text) == (impl == "xla"), impl

    def test_ledger_coalesces_inference_events(self, tiny_lm):
        cfg, params = tiny_lm
        eng = ContinuousLMEngine(cfg, params, n_slots=2, page_size=4,
                                 max_seq=16, tag="serve/t")
        for _ in range(3):
            eng.submit(np.asarray([1, 2], np.int32), max_new=2).result()
        events = [e for e in eng.ledger.events if e[0] == "inference"]
        assert len(events) == 1  # one running event per tag, not per request
        assert events[0][1] == "serve/t"


# ----------------------------------------------------------------------------
# 8-fake-device acceptance
# ----------------------------------------------------------------------------


class TestContinuousEightDevices:
    """Continuous engine under 8 fake CPU devices: serves a mixed-length
    trace, never retraces, and matches the dense baseline (device count
    is fixed at jax init, so this runs in a subprocess)."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax
import numpy as np
import jax.numpy as jnp
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.serve import ContinuousLMEngine
from repro.launch.serve import prefill_and_decode

cfg = ModelConfig(name="tiny", vocab_size=97, d_model=32, num_layers=2,
                  num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                  compute_dtype="float32", param_dtype="float32")
params = tf.init_params(jax.random.key(0), cfg)
rng = np.random.default_rng(0)
reqs = [(rng.integers(0, 97, size=l).astype(np.int32), g)
        for l, g in [(3, 5), (6, 2), (1, 4), (4, 3), (2, 6)]]
eng = ContinuousLMEngine(cfg, params, n_slots=4, page_size=4, max_seq=16)
tickets = [eng.submit(p, max_new=g) for p, g in reqs]
eng.run_until_idle()
match = all(
    t.result().tolist() == np.asarray(prefill_and_decode(
        cfg, params, jnp.asarray(p)[None], gen=g, cache_len=len(p) + g + 1
    ))[0].tolist()
    for (p, g), t in zip(reqs, tickets)
)
s = eng.stats()
print(json.dumps({
    "num_devices": jax.device_count(),
    "matches_dense": bool(match),
    "step_cache": eng.compiled_step_cache_size,
    "tokens": s["tokens"],
    "kernel_hits": eng.kernel_hits,
}))
"""

    def test_continuous_serve_on_8_devices(self, fake_devices):
        out = fake_devices(self.SCRIPT)
        assert out["num_devices"] == 8
        assert out["matches_dense"], out
        assert out["step_cache"] == 1
        # decode-step tokens: one per request comes from prefill instead
        assert out["tokens"] == (5 + 2 + 4 + 3 + 6) - 5
        assert sum(out["kernel_hits"].values()) == out["tokens"]
