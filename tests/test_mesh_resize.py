"""Serving mesh resize (ISSUE 8): the pool's owned_view/resize page-table
rewrite and the ContinuousBatcher's live migration path.

The decisive properties:
 - only rows the page tables still OWN are ever copied — freed pages'
   stale contents (live in the device arrays until reallocation) can
   never ship into the new arrays;
 - a shrink defers until live sequences fit (nothing is dropped), a grow
   applies immediately;
 - in-flight requests decode token-identically across a resize.
"""
import threading
import time

import numpy as np
import pytest

from flexflow_tpu.serving.sched import (ContinuousBatcher, PagedKVPool,
                                        PoolExhausted)
from tests.conftest import module_xla_cache
from tests.test_generate import _build_lm

# module-scoped XLA compilation cache — see conftest.module_xla_cache
_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)


@pytest.fixture(scope="module")
def lm():
    return _build_lm(2, 12)


def _prompts(lens, seed=0, vocab=50):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=(n,)).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------
# PagedKVPool.owned_view
# ---------------------------------------------------------------------
def test_owned_view_spans_follow_page_table():
    pool = PagedKVPool(num_slots=2, max_len=16, page_size=4)
    slot = pool.alloc("a", 6)  # 2 pages -> rows [0, 8)
    assert pool.owned_view("a") == [(slot, 0, 8)]
    pool.extend("a", 3)  # 9 tokens -> 3 pages -> rows [0, 12)
    assert pool.owned_view("a") == [(slot, 0, 12)]
    # freed: nothing is owned, even though the device rows still hold KV
    pool.free("a")
    assert pool.owned_view("a") == []
    assert pool.owned_view("never-allocated") == []


def test_owned_view_clamps_partial_tail_page():
    pool = PagedKVPool(num_slots=1, max_len=10, page_size=4)
    slot = pool.alloc("a", 10)  # 3 pages, last page covers rows 8..9
    assert pool.owned_view("a") == [(slot, 0, 10)]


# ---------------------------------------------------------------------
# PagedKVPool.resize
# ---------------------------------------------------------------------
def test_resize_rewrites_tables_and_freelist():
    pool = PagedKVPool(num_slots=4, max_len=16, page_size=4)
    s_a = pool.alloc("a", 5)   # slot 0
    s_b = pool.alloc("b", 3)   # slot 1
    assert (s_a, s_b) == (0, 1)
    moves = pool.resize(2)
    assert moves == [("a", 0, 0, 2), ("b", 1, 1, 1)]
    assert pool.num_slots == 2 and pool.total_pages == 2 * 4
    assert pool.free_slot_count() == 0
    with pytest.raises(PoolExhausted):
        pool.alloc("c", 1)
    # grow back: slots keep their indices, new capacity frees up
    moves = pool.resize(4)
    assert moves == [("a", 0, 0, 2), ("b", 1, 1, 1)]
    assert pool.free_slot_count() == 2
    assert pool.alloc("c", 1) in (2, 3)


def test_resize_relocates_out_of_range_slots():
    pool = PagedKVPool(num_slots=4, max_len=16, page_size=4)
    for sid in ("a", "b", "c", "d"):
        pool.alloc(sid, 5)
    pool.free("a")  # slot 0 free
    pool.free("b")  # slot 1 free
    moves = pool.resize(2)
    # c (slot 2) and d (slot 3) move into the surviving slots 0 and 1
    assert sorted(m[2] for m in moves) == [0, 1]
    for sid, old_slot, new_slot, n_pages in moves:
        assert pool.slot_of(sid) == new_slot
        assert pool.pages_of(sid) == [new_slot * pool.pages_per_slot + b
                                      for b in range(n_pages)]
        assert pool.owned_view(sid) == [(new_slot, 0, 8)]


def test_resize_refuses_when_live_exceeds_target():
    pool = PagedKVPool(num_slots=3, max_len=16, page_size=4)
    for sid in ("a", "b", "c"):
        pool.alloc(sid, 4)
    with pytest.raises(PoolExhausted, match="drain first"):
        pool.resize(2)
    # state untouched by the refusal
    assert pool.num_slots == 3 and pool.live_sequences() == 3


# ---------------------------------------------------------------------
# batcher migration
# ---------------------------------------------------------------------
def test_resize_mid_decode_token_parity_and_zero_drops(lm):
    """Shrink then grow while requests decode; every request's greedy
    tokens must match a no-resize reference run, with zero drops."""
    prompts = _prompts([6, 5, 7, 6, 5, 6])
    # staggered outputs: the two long requests are still decoding when
    # the short ones retire, so BOTH resizes migrate live sequences
    n_new = [40, 40, 16, 16, 12, 12]

    def run(resize):
        b = ContinuousBatcher(lm, max_len=48, num_slots=4, page_size=4,
                              max_queue=16)
        with b:
            handles = [b.submit(p, n) for p, n in zip(prompts, n_new)]
            if resize:
                deadline = time.monotonic() + 120
                while not any(h.tokens for h in handles):
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
                shrink = b.request_resize(2).wait(timeout=300)
                grow = b.request_resize(4).wait(timeout=300)
                assert shrink["to"] == 2 and grow["to"] == 4
                assert shrink["migrated_rows"] > 0
                assert grow["migrated_rows"] > 0
            toks = [h.result(timeout=300).tolist() for h in handles]
            assert all(h.error is None for h in handles)
        return toks, b

    ref_toks, _ = run(resize=False)
    toks, b = run(resize=True)
    assert toks == ref_toks
    assert [r["direction"] for r in b.stats()["resizes"]] \
        == ["shrink", "grow"]
    assert b.num_slots == 4 and b.pool.num_slots == 4


def _nonzero_slots(batcher):
    """Slot indices holding any nonzero KV in the (drained) batcher's
    cache arrays. Only safe AFTER the scheduler thread has exited — the
    live loop donates the caches every iteration."""
    import jax.numpy as jnp

    hot = set()
    for pair in batcher._caches.values():
        for arr in pair.values():
            # row 0 excluded: every decode iteration writes a dummy row-0
            # entry into INACTIVE slots (their outputs are discarded), so
            # only rows >= 1 distinguish real sequence KV
            sums = jnp.sum(jnp.abs(arr[:, 1:].astype(jnp.float32)),
                           axis=tuple(range(1, arr.ndim)))
            hot |= {int(s) for s in np.nonzero(np.asarray(sums))[0]}
    return hot


def test_resize_never_copies_stale_pages(lm):
    """Regression for the stale-page hazard: a finished request's rows
    stay live in the device arrays, but its pages are no longer owned —
    a resize must migrate ONLY owned rows (`owned_view`), so the
    finished sequence's KV must NOT appear in the new arrays."""
    def run(resize):
        b = ContinuousBatcher(lm, max_len=48, num_slots=3, page_size=4,
                              max_queue=8)
        with b:
            # submitted together so they land in DISTINCT slots; the
            # short one finishes first, leaving its pages freed but its
            # rows live (stale) in the device arrays while the long one
            # keeps decoding
            short = b.submit(_prompts([6], seed=1)[0], 2)
            long_req = b.submit(_prompts([6], seed=2)[0], 30)
            short.result(timeout=300)
            deadline = time.monotonic() + 120
            while not long_req.tokens:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            if resize:
                res = b.request_resize(2).wait(timeout=300)
                assert res["in_flight"] == 1
                assert res["migrated_rows"] > 0
            long_req.result(timeout=300)
            assert long_req.error is None
        return b

    # without a resize the freed slot's rows are genuinely stale-but-
    # live: the finished short request's slot AND the long one are hot
    b_ref = run(resize=False)
    assert len(_nonzero_slots(b_ref)) == 2
    # across a resize only the live sequence's owned rows shipped: the
    # stale slot's KV is gone from the new arrays
    b_res = run(resize=True)
    assert b_res.num_slots == 2
    assert len(_nonzero_slots(b_res)) == 1


def test_shrink_defers_until_live_fits_and_holds_admissions(lm):
    b = ContinuousBatcher(lm, max_len=48, num_slots=3, page_size=4,
                          max_queue=8)
    with b:
        a = b.submit(_prompts([5], seed=3)[0], 40)
        c = b.submit(_prompts([5], seed=4)[0], 40)
        deadline = time.monotonic() + 120
        while not (a.tokens and c.tokens):
            assert time.monotonic() < deadline
            time.sleep(0.005)
        ticket = b.request_resize(1)
        # two live sequences > target 1: the resize must stay deferred
        # WHILE both are live, and decoding must continue (nothing
        # dropped, no deadlock). Asserted as the invariant — deferral
        # observed only while both requests are provably unfinished —
        # not as a fixed sleep: on a warm box both 40-token budgets can
        # drain in well under any fixed sleep, and the shrink then
        # legitimately applies (the old time.sleep(0.15) form was
        # flaky for exactly that reason).
        d = None
        while not (a.done() or c.done()):
            if ticket.done() or b.num_slots != 3:
                # the apply raced the done-reads above; a retire
                # strictly precedes any apply, so re-reading done()
                # must now show it
                assert a.done() or c.done()
                break
            if d is None:
                # a request queued during the pending shrink is NOT
                # admitted to a slot (admissions are held; the ticket
                # completes strictly before any admission resumes, so
                # this read is race-free)
                d = b.submit(_prompts([5], seed=5)[0], 2)
            elif not ticket.done():
                assert not d.tokens
            assert time.monotonic() < deadline
            time.sleep(0.005)
        if d is None:
            d = b.submit(_prompts([5], seed=5)[0], 2)
        # both decoders finish -> the shrink applies -> d admits after
        a.result(timeout=300)
        c.result(timeout=300)
        res = ticket.wait(timeout=300)
        assert res["to"] == 1 and b.num_slots == 1
        assert d.result(timeout=300).size == 2


def test_concurrent_admissions_during_deferred_shrink_queue_not_429(lm):
    """Regression (ISSUE 12 satellite): while a shrink DEFERS (live >
    target), concurrent submits must be ADMITTED and held queued — the
    admission gate only meters queue count and backlog pages, so a
    pending resize must surface as waiting, never as a 429 — and every
    held request must run once capacity returns."""
    from flexflow_tpu.serving.sched import AdmissionError

    b = ContinuousBatcher(lm, max_len=96, num_slots=3, page_size=4,
                          max_queue=16)
    # a pass of the loop takes at least 10 ms, so the two decoders stay
    # live for most of a second however fast the toy step is (with a warm
    # compile cache 80 tokens used to finish inside the 0.2 s below)
    b.fault_hook = lambda _b: time.sleep(0.01)
    with b:
        # long enough that the deferred window is seconds wide — the
        # mid-shrink asserts below must run while both are still live
        long_a = b.submit(_prompts([5], seed=20)[0], 80)
        long_b = b.submit(_prompts([5], seed=21)[0], 80)
        deadline = time.monotonic() + 120
        while not (long_a.tokens and long_b.tokens):
            assert time.monotonic() < deadline
            time.sleep(0.005)
        ticket = b.request_resize(1)  # defers: 2 live > 1
        errors = []
        held = [None] * 4

        def _submit(i):
            try:
                held[i] = b.submit(_prompts([4], seed=30 + i)[0], 2)
            except AdmissionError as e:
                errors.append(e)

        threads = [threading.Thread(target=_submit, args=(i,))
                   for i in range(len(held))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # all admitted — zero 429s — but none scheduled while the shrink
        # is pending (admissions are held, not rejected)
        assert not errors
        time.sleep(0.2)  # a buggy scheduler would run them right away
        assert not ticket.done()
        assert all(not h.tokens for h in held)
        assert b.admission.queue_depth() == len(held)
        assert b.queued_prefill_tokens() == sum(4 for _ in held)
        # the decoders finish -> shrink applies -> the held queue drains
        long_a.result(timeout=300)
        long_b.result(timeout=300)
        assert ticket.wait(timeout=300)["to"] == 1
        for h in held:
            assert h.result(timeout=300).size == 2
        assert all(h.error is None for h in held)


def test_resize_rejected_while_pending_and_after_stop(lm):
    from flexflow_tpu.serving import BatcherStopped

    b = ContinuousBatcher(lm, max_len=48, num_slots=2, page_size=4,
                          max_queue=4)
    with b:
        r = b.submit(_prompts([5], seed=6)[0], 40)
        deadline = time.monotonic() + 120
        while not r.tokens:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # target 1 < live 1? live == 1 fits -> use a second live request
        r2 = b.submit(_prompts([5], seed=7)[0], 40)
        while not r2.tokens:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        ticket = b.request_resize(1)  # defers: 2 live > 1
        with pytest.raises(RuntimeError, match="already pending"):
            b.request_resize(2)
        r.result(timeout=300)
        r2.result(timeout=300)
        ticket.wait(timeout=300)
    with pytest.raises(BatcherStopped):
        b.request_resize(2)


def test_resize_applies_while_idle(lm):
    b = ContinuousBatcher(lm, max_len=48, num_slots=2, page_size=4,
                          max_queue=4)
    with b:
        res = b.request_resize(4).wait(timeout=300)
        assert res["to"] == 4 and res["migrated_rows"] == 0
        out = b.submit(_prompts([5], seed=8)[0], 3).result(timeout=300)
        assert out.size == 3
