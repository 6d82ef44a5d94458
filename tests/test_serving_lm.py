"""Continuous batching + paged KV cache (ISSUE 8).

The correctness gate: per-request generated tokens under continuous
batching are BITWISE-identical to the same request decoded alone
through ``Transformer.decode_chunk`` (greedy) — including requests that
join mid-flight, finish early on EOS, or are evicted on deadline — and
hot model swap never mixes versions within one request's continuation.
The KV-leak gate: every block returns to the free list on every
completion/eviction path and ``serve/kv_blocks_in_use`` drains to zero
at shutdown.

The solo oracle decodes through DENSE ``decode_chunk`` with the same
prefill chunking, duplicated to batch rows of 2 — the scheduler's gemm
M-class floor (XLA CPU's 1-row gemv differs from every >=2-row gemm in
the last ulp; all >=2-row shapes agree bitwise row-for-row, which the
bucket floor of 2 turns into batch-mix independence).
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import observability as obs
from bigdl_tpu.models.transformer_lm import TransformerLM
from serving_helpers import no_leaked_blocks, solo_oracle as _oracle
from bigdl_tpu.serving import (DeadlineExceeded, DecodeScheduler,
                               KVCacheOOM, PagedKVCache, QueueFull,
                               blocks_for_tokens,
                               decode_scheduler_threads_alive,
                               prefill_schedule)

V, H, LAYERS = 48, 32, 2
MAXLEN = 256
CHUNK = 8


def _model(**kw):
    cfg = dict(vocab_size=V, hidden_size=H, num_heads=4, filter_size=64,
               num_layers=LAYERS, max_len=MAXLEN)
    cfg.update(kw)
    m = TransformerLM(**cfg)
    m.ensure_initialized()
    return m


_shared = {}


def shared_model():
    if "m" not in _shared:
        _shared["m"] = _model(pos_encoding="rope", num_kv_heads=2)
    return _shared["m"]


def solo_oracle(model, params, prompt, max_new, chunk=CHUNK, eos_id=None):
    return _oracle(model, params, prompt, max_new, chunk=chunk,
                   maxlen=MAXLEN, eos_id=eos_id)


def _no_leaked_blocks(st):
    no_leaked_blocks(st)


def _sched(model, **kw):
    cfg = dict(max_slots=4, block_size=4, max_seq_len=96,
               prefill_chunk=CHUNK)
    cfg.update(kw)
    return DecodeScheduler(model, **cfg)


def _set_paged_path(request, monkeypatch):
    if request.param == "kernel":
        monkeypatch.setenv("BIGDL_TPU_PAGED_ATTN", "interpret")
    else:
        monkeypatch.delenv("BIGDL_TPU_PAGED_ATTN", raising=False)
    return request.param


@pytest.fixture(params=["dense",
                        pytest.param("kernel", marks=pytest.mark.slow)])
def paged_path(request, monkeypatch):
    """The ISSUE 11 kernel-on/kernel-off matrix: 'kernel' routes
    Attention.decode_paged through the Pallas paged-attention kernel
    (interpret mode on CPU — the identical kernel the TPU compiles);
    'dense' keeps the gathered-view einsum. The solo oracle always
    decodes DENSE (decode_chunk), so the kernel arm asserts the hard
    claim: kernel tokens are bitwise the dense tokens.

    The kernel arm rides @slow (tier-1 wall-time budget): kernel decode
    stays gated in tier-1 by test_kv_spill[kernel], test_serving_mesh's
    paged-kernel TP test, and the kernels/serve/chaos smokes; `make
    test-slow` runs the full matrix."""
    return _set_paged_path(request, monkeypatch)


@pytest.fixture(params=["dense",
                        pytest.param("kernel", marks=pytest.mark.slow)])
def paged_path_heavy(request, monkeypatch):
    """Same matrix, but the kernel arm is @slow: interpret-mode Pallas
    multiplies these tests' cost ~3x and the bitwise kernel claim is
    already pinned in tier-1 by the lighter gates (the solo oracle,
    the batched-spec matrix, kernels-smoke) — the heavy churn variants
    re-prove it on the full run only (ROADMAP tier-1 budget watch)."""
    return _set_paged_path(request, monkeypatch)


def _spy_guard(paged_path):
    """Returns a closure asserting the Pallas path actually built the
    programs that served the traffic (trace-count spy)."""
    from bigdl_tpu.kernels import paged_attention as pk
    before = pk.trace_count()

    def check():
        if paged_path == "kernel":
            assert pk.trace_count() > before, \
                "kernel arm served traffic without tracing the Pallas path"
        else:
            assert pk.trace_count() == before
    return check


# ---------------------------------------------------------------------------
# paged attention vs dense decode_chunk
# ---------------------------------------------------------------------------

def test_paged_decode_bitwise_vs_dense():
    """decode_paged over gathered blocks == decode_chunk over a dense
    cache for the same batch (history + one step; RoPE+GQA model —
    per-row rotary positions and the grouped einsum both covered): the
    argmax tokens equal and the logits within 1e-6 absolute. That is
    what XLA-CPU can give: the two steps contract the same sum over
    differently shaped operands (gathered blocks against one dense
    cache), and two contractions of one sum differ in the last ulp
    (0.116624 against 0.11662401 here; ROADMAP Design 8)."""
    m = shared_model()
    p = m.params
    B, bs, mbs = 4, 4, 8
    nblocks = 1 + B * mbs
    pages = [(jnp.zeros((nblocks, 2, bs, H // 4)),) * 2 for _ in m.blocks]
    tables = np.zeros((B, mbs), np.int32)
    for b in range(B):
        tables[b] = 1 + b * mbs + np.arange(mbs)
    tables = jnp.asarray(tables)
    rng = np.random.RandomState(0)
    toks = rng.randint(1, V, size=(B, 10)).astype(np.int32)
    step = jax.jit(lambda t, po, pg: m.decode_paged(p, t, po, pg, tables))
    dense = jax.jit(lambda t, po, c: m.decode_chunk(p, t, po, c))
    caches = m.init_cache(B, 64, jnp.float32)
    lg_p = lg_d = None
    for t in range(10):
        ps = jnp.full((B,), t, jnp.int32)
        lg_p, pages = step(jnp.asarray(toks[:, t:t + 1]), ps, pages)
        lg_d, caches = dense(jnp.asarray(toks[:, t:t + 1]), jnp.int32(t),
                             caches)
    lg_p, lg_d = np.asarray(lg_p), np.asarray(lg_d)
    assert np.array_equal(lg_p.argmax(-1), lg_d.argmax(-1))
    np.testing.assert_allclose(lg_p, lg_d, rtol=0, atol=1e-6)


def test_prefill_schedule():
    assert prefill_schedule(1, 8) == [(0, 1, 2)]
    assert prefill_schedule(8, 8) == [(0, 8, 8)]
    assert prefill_schedule(11, 8) == [(0, 8, 8), (8, 3, 4)]
    assert prefill_schedule(17, 8) == [(0, 8, 8), (8, 8, 8), (16, 1, 2)]


# ---------------------------------------------------------------------------
# the correctness gate
# ---------------------------------------------------------------------------

def test_continuous_batching_bitwise_solo_oracle(paged_path):
    """Mixed-length requests joining mid-flight and finishing early:
    every request's tokens are bitwise-identical to its solo decode —
    through the dense gather AND through the Pallas paged kernel
    (chunked prefill and mid-flight joins ride the same matrix)."""
    m = shared_model()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (3, 11, 7, 18, 5, 25)]
    maxnews = [6, 12, 4, 9, 15, 5]
    spy = _spy_guard(paged_path)
    with _sched(m) as sched:
        futs = []
        for i, (pr, mn) in enumerate(zip(prompts, maxnews)):
            futs.append(sched.submit(pr, mn))
            if i in (2, 4):
                time.sleep(0.03)   # stagger arrivals → mid-flight joins
        results = [f.result(timeout=120) for f in futs]
        st = sched.stats()
    spy()
    assert st["completed"] == len(prompts)
    for i, (pr, mn) in enumerate(zip(prompts, maxnews)):
        want = solo_oracle(m, m.params, pr, mn)
        assert np.array_equal(results[i], want), f"request {i} diverged"
    _no_leaked_blocks(st)
    assert decode_scheduler_threads_alive() == 0


def test_eos_finishes_early_and_frees_blocks():
    m = shared_model()
    rng = np.random.RandomState(1)
    pr = rng.randint(1, V, size=9).astype(np.int32)
    free_ref = solo_oracle(m, m.params, pr, 20)
    # pick the 3rd generated token as "EOS" so the run must stop there
    eos = int(free_ref[2])
    want = solo_oracle(m, m.params, pr, 20, eos_id=eos)
    with _sched(m, eos_id=eos) as sched:
        got = sched.submit(pr, 20).result(timeout=120)
        st = sched.stats()
    assert np.array_equal(got, want)
    assert got.size < 20 and got[-1] == eos
    _no_leaked_blocks(st)


def test_deadline_eviction_partial_prefix_bitwise():
    """A request evicted on deadline fails typed, its blocks return to
    the free list, and the partial tokens it DID generate are a bitwise
    prefix of the solo decode."""
    m = shared_model()
    rng = np.random.RandomState(2)
    pr = rng.randint(1, V, size=6).astype(np.int32)
    want = solo_oracle(m, m.params, pr, 60)
    with _sched(m, max_seq_len=160) as sched:
        # 150 decode steps cannot finish inside 75ms (a step costs ~1ms
        # warm on this box) — the deadline must evict mid-generation
        fut = sched.submit(pr, 150, deadline_ms=75.0)
        with pytest.raises(DeadlineExceeded) as ei:
            fut.result(timeout=120)
        st = sched.stats()
    partial = ei.value.partial
    assert 0 < partial.size < 150
    if partial.size > 60:
        partial = partial[:60]  # oracle computed 60 — compare the prefix
    assert np.array_equal(partial, want[:partial.size])
    assert st["timeouts"] == 1
    _no_leaked_blocks(st)


def test_hot_swap_never_mixes_versions():
    """Requests in flight at swap() keep their admission version to the
    last token (bitwise vs THAT version's solo oracle); requests
    admitted after the swap serve the new version."""
    m = shared_model()
    m2 = _model(pos_encoding="rope", num_kv_heads=2)  # fresh init = v1
    rng = np.random.RandomState(3)
    pr_old = rng.randint(1, V, size=10).astype(np.int32)
    pr_new = rng.randint(1, V, size=10).astype(np.int32)
    with _sched(m) as sched:
        f_old = sched.submit(pr_old, 24)
        time.sleep(0.05)           # let it admit and start decoding
        v1 = sched.swap(m2.params, m2.state)
        f_new = sched.submit(pr_new, 8)
        old = f_old.result(timeout=120)
        new = f_new.result(timeout=120)
    assert f_old.version == "v0" and f_new.version == v1
    assert np.array_equal(old, solo_oracle(m, m.params, pr_old, 24))
    assert np.array_equal(new, solo_oracle(m, m2.params, pr_new, 8))


def test_speculative_fast_path_bitwise_and_fewer_rounds(paged_path):
    """Greedy speculative decoding inside the scheduler is output-
    preserving; with the target as its own draft, acceptance is total
    and verify rounds collapse ~(k+1)-fold. The kernel arm drives the
    S=k+1 verify-chunk shape through the Pallas path too."""
    m = _model()   # sinusoidal/MHA variant exercises the other PE path
    rng = np.random.RandomState(4)
    pr = rng.randint(1, V, size=9).astype(np.int32)
    want = solo_oracle(m, m.params, pr, 12)
    spy = _spy_guard(paged_path)
    with _sched(m, draft_model=m, spec_k=3) as sched:
        got = sched.submit(pr, 12).result(timeout=120)
        st = sched.stats()
    spy()
    assert np.array_equal(got, want)
    assert st["spec_rounds"] > 0
    assert st["spec_accepted"] >= st["spec_rounds"]  # perfect draft
    assert st["decode_steps"] < 12                   # fewer than 1/token
    _no_leaked_blocks(st)


@pytest.mark.slow
def test_spec_covers_the_whole_batch():
    """ISSUE 14: speculation is no longer a solo fast path — two
    concurrent greedy requests ride ONE batched spec round per step
    boundary, each advancing by its own acceptance length, and both
    stay bitwise-correct. With a perfect draft the verify dispatches
    collapse ~(k+1)-fold for the whole batch, not just a lone row."""
    m = _model()
    rng = np.random.RandomState(5)
    p1 = rng.randint(1, V, size=7).astype(np.int32)
    p2 = rng.randint(1, V, size=13).astype(np.int32)
    with _sched(m, draft_model=m, spec_k=3) as sched:
        f1 = sched.submit(p1, 10)
        f2 = sched.submit(p2, 10)
        r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
        st = sched.stats()
    assert np.array_equal(r1, solo_oracle(m, m.params, p1, 10))
    assert np.array_equal(r2, solo_oracle(m, m.params, p2, 10))
    assert st["spec_rounds"] > 0
    # both rows rode rounds: row-rounds exceed dispatch rounds
    assert st["spec_row_rounds"] > st["spec_rounds"]
    # 20 tokens total; perfect-draft batched spec needs far fewer than
    # one verify dispatch per token (2 joined prefills cost ~3 rounds)
    assert st["decode_steps"] <= 10


# ---------------------------------------------------------------------------
# batched speculative decoding (ISSUE 14): the matrix
# ---------------------------------------------------------------------------

def test_batched_spec_bitwise_with_joins(paged_path):
    """THE batched-spec gate: mixed-length greedy requests joining
    mid-flight all ride the spec rounds (draft = target, so acceptance
    is total), every request's tokens are BITWISE its solo dense
    decode — through the dense gather AND the Pallas kernel (which
    serves the (bucket>1, S=spec_k+1) verify shape here) — and live
    traffic adds ZERO compiled shapes past warmup."""
    m = shared_model()
    rng = np.random.RandomState(40)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (3, 11, 7, 18, 5)]
    maxnews = [6, 12, 4, 9, 15]
    spy = _spy_guard(paged_path)
    sched = _sched(m, draft_model=m, spec_k=3)
    sched.start(warmup=True)
    try:
        n0 = sched._step_jit.compiled_shape_count()
        d0 = sched._draft_jit.compiled_shape_count()
        futs = []
        for i, (pr, mn) in enumerate(zip(prompts, maxnews)):
            futs.append(sched.submit(pr, mn))
            if i in (1, 3):
                time.sleep(0.03)   # stagger arrivals → mid-flight joins
        results = [f.result(timeout=120) for f in futs]
        assert sched._step_jit.compiled_shape_count() == n0
        assert sched._draft_jit.compiled_shape_count() == d0
        st = sched.stats()
    finally:
        sched.shutdown()
    spy()
    for i, (pr, mn) in enumerate(zip(prompts, maxnews)):
        want = solo_oracle(m, m.params, pr, mn)
        assert np.array_equal(results[i], want), f"request {i} diverged"
    assert st["spec_rounds"] > 0
    assert st["spec_row_rounds"] >= st["spec_rounds"]
    # the dispatch-amortization claim: with total acceptance the batch
    # needs far fewer verify dispatches than tokens
    assert st["decode_steps"] < sum(maxnews) // 2
    _no_leaked_blocks(sched.stats())
    assert decode_scheduler_threads_alive() == 0


def test_batched_spec_weak_draft_rollback_bitwise(paged_path_heavy):
    """The PER-ROW ROLLBACK gate: a randomly-initialized 1-layer draft
    disagrees with the target almost everywhere, so nearly every round
    REJECTS at some per-row prefix — positions past each row's accepted
    length hold garbage that the next round must overwrite, per row,
    with rows at different acceptance depths. Tokens must stay bitwise
    the solo oracle anyway (speculation is output-preserving under any
    acceptance), on both attention paths (the kernel program is the
    same one the joins gate drives in tier-1; its rejection-path rerun
    rides the full-matrix run)."""
    paged_path = paged_path_heavy
    m = shared_model()
    draft = _model(num_layers=1, pos_encoding="rope", num_kv_heads=2)
    rng = np.random.RandomState(41)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (4, 9, 14)]
    spy = _spy_guard(paged_path)
    with _sched(m, draft_model=draft, spec_k=3) as sched:
        futs = [sched.submit(p, 8) for p in prompts]
        results = [f.result(timeout=120) for f in futs]
        st = sched.stats()
    spy()
    for i, p in enumerate(prompts):
        assert np.array_equal(results[i], solo_oracle(m, m.params, p, 8)), \
            f"request {i} diverged under rejection/rollback"
    assert st["spec_rounds"] > 0
    # a random draft over a 48-token vocab must reject sometimes —
    # otherwise this test exercises nothing
    assert st["spec_accepted"] < 3 * st["spec_row_rounds"]
    _no_leaked_blocks(st)


@pytest.mark.slow
def test_batched_spec_eos_finishes_one_row_mid_round():
    """A row hitting EOS inside a spec round finishes and frees its
    blocks while the other rows keep riding rounds — and the EOS'd
    row's output is bitwise the EOS-stopped oracle."""
    m = shared_model()
    rng = np.random.RandomState(42)
    p1 = rng.randint(1, V, size=9).astype(np.int32)
    p2 = rng.randint(1, V, size=6).astype(np.int32)
    free_ref = solo_oracle(m, m.params, p1, 20)
    eos = int(free_ref[2])            # stop p1 at its 3rd token
    want1 = solo_oracle(m, m.params, p1, 20, eos_id=eos)
    want2 = solo_oracle(m, m.params, p2, 12, eos_id=eos)
    with _sched(m, draft_model=m, spec_k=3, eos_id=eos) as sched:
        f1 = sched.submit(p1, 20)
        f2 = sched.submit(p2, 12)
        r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
        st = sched.stats()
    assert np.array_equal(r1, want1) and r1[-1] == eos and r1.size < 20
    assert np.array_equal(r2, want2)
    assert st["spec_rounds"] > 0
    _no_leaked_blocks(st)


def test_batched_spec_deadline_eviction_partial_prefix(paged_path_heavy):
    """A deadline eviction between spec rounds fails the row typed with
    a partial that is a bitwise prefix of the solo decode, while the
    surviving row completes bitwise."""
    m = shared_model()
    rng = np.random.RandomState(43)
    pr = rng.randint(1, V, size=6).astype(np.int32)
    p2 = rng.randint(1, V, size=5).astype(np.int32)
    want = solo_oracle(m, m.params, pr, 60)
    spy = _spy_guard(paged_path_heavy)
    with _sched(m, draft_model=m, spec_k=3, max_seq_len=160) as sched:
        fut = sched.submit(pr, 140, deadline_ms=60.0)
        f2 = sched.submit(p2, 10)
        with pytest.raises(DeadlineExceeded) as ei:
            fut.result(timeout=120)
        r2 = f2.result(timeout=120)
        st = sched.stats()
    spy()
    partial = ei.value.partial
    assert 0 < partial.size < 140
    if partial.size > 60:
        partial = partial[:60]
    assert np.array_equal(partial, want[:partial.size])
    assert np.array_equal(r2, solo_oracle(m, m.params, p2, 10))
    assert st["timeouts"] == 1
    _no_leaked_blocks(st)


def test_batched_spec_defrag_then_spec(paged_path_heavy):
    """Defrag between spec rounds rewrites BOTH pools' tables; the
    next rounds read the moved pages and tokens stay bitwise."""
    m = shared_model()
    rng = np.random.RandomState(44)
    pr = rng.randint(1, V, size=5).astype(np.int32)
    spy = _spy_guard(paged_path_heavy)
    with _sched(m, draft_model=m, spec_k=3,
                num_blocks=4 * 24 + 1) as sched:
        for _ in range(2):   # churn fragments both pools' id spaces
            fs = [sched.submit(rng.randint(1, V, size=n), 3)
                  for n in (4, 9, 6)]
            [f.result(timeout=120) for f in fs]
        f_live = sched.submit(pr, 30)
        time.sleep(0.05)
        sched.defrag()       # deferred to the next step boundary
        out = f_live.result(timeout=120)
        st = sched.stats()
    spy()
    assert np.array_equal(out, solo_oracle(m, m.params, pr, 30))
    assert st["spec_rounds"] > 0
    _no_leaked_blocks(st)


def test_batched_spec_prefix_hit_kernel_matrix(paged_path_heavy):
    """The warm-hit spec path (lazy draft catch-up) through the kernel
    matrix: warm tokens bitwise cold, and the warm request speculates
    (the detailed acceptance gate lives in test_prefix_cache.py)."""
    m = shared_model()
    rng = np.random.RandomState(45)
    p = rng.randint(1, V, size=16).astype(np.int32)
    want = solo_oracle(m, m.params, p, 10)
    spy = _spy_guard(paged_path_heavy)
    with _sched(m, draft_model=m, spec_k=3) as sched:
        a = sched.submit(p, 10).result(timeout=120)
        rounds_cold = sched.stats()["spec_rounds"]
        b = sched.submit(p, 10).result(timeout=120)
        st = sched.stats()
    spy()
    assert np.array_equal(a, want) and np.array_equal(b, want)
    assert st["prefix_hits"] == 1
    assert st["spec_rounds"] > rounds_cold, "warm hit must speculate"
    _no_leaked_blocks(st)


@pytest.mark.slow
def test_batched_spec_mixed_sampled_rows_untouched():
    """The mixed-batch gate: sampled rows ride the spec dispatch masked
    to ONE real token — their tokens are bitwise what they draw with no
    draft armed (same seed ⇒ same stream, spec company or not), they
    ride zero spec rounds of their own, and the greedy rows sharing the
    batch still speculate bitwise."""
    m = shared_model()
    rng = np.random.RandomState(46)
    p_s = rng.randint(1, V, size=6).astype(np.int32)
    p_g = rng.randint(1, V, size=9).astype(np.int32)
    kw = dict(temperature=0.9, top_p=0.9, seed=321)
    want_sampled = _one(m, p_s, max_new=10, **kw)   # no draft armed
    want_greedy = solo_oracle(m, m.params, p_g, 10)
    with _sched(m, draft_model=m, spec_k=3) as sched:
        f_g = sched.submit(p_g, 10)
        f_s = sched.submit(p_s, 10, **kw)
        got_g = np.asarray(f_g.result(timeout=120))
        got_s = np.asarray(f_s.result(timeout=120))
        st = sched.stats()
    assert np.array_equal(got_s, want_sampled), \
        "sampled tokens must not depend on spec company"
    assert np.array_equal(got_g, want_greedy)
    assert st["spec_rounds"] > 0, "the greedy row must still speculate"
    assert f_s.trace["spec_rounds"] == 0 and f_s.trace["spec_accepted"] == 0
    assert f_g.trace["spec_rounds"] > 0


# ---------------------------------------------------------------------------
# KV block accounting
# ---------------------------------------------------------------------------

def test_kv_ledger_alloc_free_oom():
    m = shared_model()
    kv = PagedKVCache(m, num_blocks=9, block_size=4, max_blocks_per_seq=4)
    assert kv.stats()["blocks_total"] == 8
    kv.ensure_capacity("a", 10)        # 3 blocks
    assert kv.owned("a") == 3 and kv.blocks_free() == 5
    kv.ensure_capacity("a", 10)        # idempotent
    assert kv.owned("a") == 3
    kv.ensure_capacity("b", 16)        # 4 blocks
    assert kv.blocks_free() == 1
    with pytest.raises(KVCacheOOM):
        kv.ensure_capacity("c", 8)     # needs 2, only 1 free
    assert kv.owned("c") == 0          # failed alloc takes NOTHING
    with pytest.raises(ValueError):
        kv.ensure_capacity("a", 17)    # past the table width
    assert kv.free("a") == 3
    assert kv.free("a") == 0           # double-free is a no-op
    kv.ensure_capacity("c", 8)         # now fits
    kv.free("b"), kv.free("c")
    s = kv.stats()
    assert s["blocks_in_use"] == 0 and s["blocks_free"] == 8
    assert s["high_water"] == 7
    tbl = kv.block_table("gone")
    assert tbl.shape == (4,) and (tbl == 0).all()
    assert blocks_for_tokens(1, 4) == 1 and blocks_for_tokens(9, 4) == 3


def test_kv_ledger_truncate_rollback():
    """The per-row rollback primitive: truncate drops only the TAIL of
    an owner's table, is refcount-aware (a shared tail page survives
    for its other referent), and is idempotent past the allocation."""
    m = shared_model()
    kv = PagedKVCache(m, num_blocks=9, block_size=4, max_blocks_per_seq=6)
    kv.ensure_capacity("a", 20)        # 5 blocks
    a_blocks = kv.owner_blocks("a")
    assert kv.truncate("a", 9) == 2    # keep ceil(9/4)=3, drop 2
    assert kv.owner_blocks("a") == a_blocks[:3]
    assert kv.blocks_free() == 5
    assert kv.truncate("a", 12) == 0   # idempotent past the allocation
    assert kv.truncate("unknown", 4) == 0
    # shared tail: adopt a's last block into b's table, then truncate a
    kv.adopt("b", [a_blocks[2]])
    assert kv.block_refs(a_blocks[2]) == 2
    assert kv.truncate("a", 4) == 2    # drops 2 table entries...
    assert kv.block_refs(a_blocks[2]) == 1   # ...but the shared page
    assert kv.owned("b") == 1                # lives on for b
    assert kv.truncate("a", 0) == 1
    kv.free("a"), kv.free("b")
    s = kv.stats()
    assert s["blocks_in_use"] == 0 and s["blocks_free"] == 8
    assert kv.audit(prefix_pins={})["ok"]


def test_kv_defrag_repacks_and_preserves_decode(paged_path_heavy):
    """Churn scatters live blocks across the pool; defrag repacks them
    to the low end (frag -> 0) and the moved pages still decode
    bitwise — on both attention paths (the kernel arm reads the moved
    pages through rewritten tables: defrag-then-decode)."""
    paged_path = paged_path_heavy
    m = shared_model()
    rng = np.random.RandomState(6)
    pr = rng.randint(1, V, size=5).astype(np.int32)
    spy = _spy_guard(paged_path)
    with _sched(m, num_blocks=4 * 24 + 1) as sched:
        # churn: waves of short requests fragment the id space
        for _ in range(3):
            fs = [sched.submit(rng.randint(1, V, size=n), 3)
                  for n in (4, 9, 6, 12)]
            [f.result(timeout=120) for f in fs]
        # hold one request mid-flight... simplest: measure frag after
        # churn, then defrag with live allocations present
        f_live = sched.submit(pr, 30)
        time.sleep(0.08)   # admitted, decoding
        frag_before = sched.kv.frag_blocks()
        sched.defrag()     # deferred to the next step boundary
        out = f_live.result(timeout=120)
        st = sched.stats()
    spy()
    assert np.array_equal(out, solo_oracle(m, m.params, pr, 30))
    assert st["defrags"] >= 0 and sched.kv.frag_blocks() <= frag_before
    _no_leaked_blocks(st)


def test_admission_backpressure_on_block_exhaustion():
    """A pool too small for two concurrent requests serves them one
    after the other instead of OOMing mid-flight — admission defers
    until eviction frees blocks."""
    m = shared_model()
    rng = np.random.RandomState(8)
    p1 = rng.randint(1, V, size=20).astype(np.int32)
    p2 = rng.randint(1, V, size=20).astype(np.int32)
    # each request needs ceil((20+8)/4)=7 blocks; pool holds 9
    with _sched(m, num_blocks=10, max_seq_len=32) as sched:
        f1 = sched.submit(p1, 8)
        f2 = sched.submit(p2, 8)
        r1, r2 = f1.result(timeout=120), f2.result(timeout=120)
        st = sched.stats()
    assert np.array_equal(r1, solo_oracle(m, m.params, p1, 8))
    assert np.array_equal(r2, solo_oracle(m, m.params, p2, 8))
    _no_leaked_blocks(st)


def test_kv_gauges_exported():
    obs.enable()
    try:
        m = shared_model()
        kv = PagedKVCache(m, num_blocks=5, block_size=4,
                          max_blocks_per_seq=2)
        kv.ensure_capacity("x", 8)
        reg = obs.registry()
        assert reg.get("serve/kv_blocks_in_use").value == 2
        assert reg.get("serve/kv_blocks_free").value == 2
        assert reg.get("serve/kv_blocks_total").value == 4
        kv.free("x")
        assert reg.get("serve/kv_blocks_in_use").value == 0
        assert reg.get("serve/kv_allocs").value >= 2
        assert reg.get("serve/kv_frees").value >= 2
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# engine behavior
# ---------------------------------------------------------------------------

def test_one_compiled_step_no_recompiles_mid_traffic():
    """After warmup, serving mixed-length traffic adds ZERO compiled
    shapes — the whole point of slots+buckets+paging."""
    m = shared_model()
    sched = _sched(m)
    sched.start(warmup=True)
    try:
        n0 = sched._step_jit.compiled_shape_count()
        rng = np.random.RandomState(9)
        fs = [sched.submit(rng.randint(1, V, size=n), mn)
              for n, mn in ((3, 5), (11, 8), (22, 4), (7, 9), (15, 3))]
        [f.result(timeout=120) for f in fs]
        assert sched._step_jit.compiled_shape_count() == n0
    finally:
        sched.shutdown()


def test_rejection_and_typed_errors():
    m = shared_model()
    sched = _sched(m, max_queue=2)
    # not started: submissions queue; overflow rejects typed
    sched.submit(np.arange(1, 4), 2)
    sched.submit(np.arange(1, 4), 2)
    with pytest.raises(QueueFull):
        sched.submit(np.arange(1, 4), 2)
    with pytest.raises(ValueError):
        sched.submit(np.arange(1, 4), 0)          # max_new < 1
    with pytest.raises(ValueError):
        sched.submit([], 4)                        # empty prompt
    with pytest.raises(ValueError):
        sched.submit(np.arange(1, 90), 80)         # over max_seq_len
    sched.start(warmup=False)
    sched.shutdown(drain=True)
    assert sched.stats()["completed"] == 2
    _no_leaked_blocks(sched.stats())
    assert decode_scheduler_threads_alive() == 0


def test_shutdown_no_drain_fails_typed_and_frees():
    from bigdl_tpu.serving import EngineStopped
    m = shared_model()
    sched = _sched(m)
    futs = [sched.submit(np.arange(1, 10), 30) for _ in range(3)]
    sched.start(warmup=False)
    time.sleep(0.05)
    sched.shutdown(drain=False)
    for f in futs:
        if f.exception() is not None:
            assert isinstance(f.exception(), EngineStopped)
    _no_leaked_blocks(sched.stats())
    assert decode_scheduler_threads_alive() == 0
    with pytest.raises(EngineStopped):
        sched.submit(np.arange(1, 4), 2)


def test_ttft_tpot_trace_and_metrics():
    obs.enable()
    try:
        m = shared_model()
        with _sched(m) as sched:
            fut = sched.submit(np.arange(1, 8), 6)
            out = fut.result(timeout=120)
        tr = fut.trace
        assert tr is not None and tr["tokens"] == out.size == 6
        assert tr["ttft_ms"] > 0 and tr["prefill_ms"] > 0
        assert tr["tpot_ms"] > 0 and tr["decode_steps"] == 5
        assert tr["version"] == "v0" and tr["rid"] == fut.rid
        reg = obs.registry()
        assert reg.get("serve/ttft_ms").count >= 1
        assert reg.get("serve/tpot_ms").count >= 1
        assert reg.get("serve/lm_tokens").value >= 6
        assert reg.get("serve/kv_blocks_in_use").value == 0
    finally:
        obs.disable()


@pytest.mark.slow
def test_static_admission_is_whole_request_batching():
    """The bench baseline: with admission='static' a second wave only
    admits after the first fully drains — but results stay bitwise."""
    m = shared_model()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (5, 9, 6, 12)]
    with _sched(m, admission="static", max_slots=2) as sched:
        futs = [sched.submit(p, 6) for p in prompts]
        results = [f.result(timeout=120) for f in futs]
        st = sched.stats()
    for p, r in zip(prompts, results):
        assert np.array_equal(r, solo_oracle(m, m.params, p, 6))
    _no_leaked_blocks(st)


# ---------------------------------------------------------------------------
# sampling (ISSUE 10 satellite): temperature / top-p with seeded streams
# ---------------------------------------------------------------------------


def _one(m, prompt, max_new=10, **kw):
    with _sched(m) as sched:
        return np.asarray(sched.submit(prompt, max_new, **kw)
                          .result(timeout=120))


def test_sampling_default_and_temp0_stay_greedy_bitwise():
    """temperature=0 (the default, and explicitly with a seed set) is
    BITWISE the greedy path — the pre-sampling correctness gate."""
    m = shared_model()
    p = np.random.RandomState(20).randint(1, V, size=7).astype(np.int32)
    want = solo_oracle(m, m.params, p, 10)
    assert np.array_equal(_one(m, p), want)
    assert np.array_equal(_one(m, p, temperature=0.0, seed=99), want)


@pytest.mark.slow
def test_sampling_seeded_reproducible_and_batch_mix_independent():
    """Same seed ⇒ same tokens — alone or sharing the batch with other
    traffic (keys derive from (seed, position) only, the sampling
    analog of the gemm M-class floor)."""
    m = shared_model()
    rng = np.random.RandomState(21)
    p = rng.randint(1, V, size=6).astype(np.int32)
    kw = dict(temperature=0.9, top_p=0.9, seed=123)
    solo1 = _one(m, p, **kw)
    solo2 = _one(m, p, **kw)
    assert np.array_equal(solo1, solo2), "same seed must reproduce"
    with _sched(m) as sched:
        others = [sched.submit(rng.randint(1, V, size=5).astype(np.int32),
                               8) for _ in range(2)]
        fut = sched.submit(p, 10, **kw)
        mixed = np.asarray(fut.result(timeout=120))
        for f in others:
            f.result(timeout=120)
    assert np.array_equal(mixed, solo1), \
        "sampled tokens must not depend on batch mix"
    diff_seed = _one(m, p, temperature=0.9, top_p=0.9, seed=124)
    assert not np.array_equal(solo1, diff_seed) or solo1.size < 3


def test_sampling_top_p_collapse_is_greedy():
    """top_p → 0 keeps only the top-1 token: sampling must reduce to
    the greedy choice exactly."""
    m = shared_model()
    p = np.random.RandomState(22).randint(1, V, size=5).astype(np.int32)
    want = solo_oracle(m, m.params, p, 8)
    got = _one(m, p, max_new=8, temperature=0.8, top_p=1e-6, seed=7)
    assert np.array_equal(got, want)


def test_sampling_validation_and_greedy_rows_unaffected():
    m = shared_model()
    p = np.asarray([1, 2, 3], np.int32)
    with _sched(m) as sched:
        with pytest.raises(ValueError, match="temperature"):
            sched.submit(p, 4, temperature=-0.1)
        with pytest.raises(ValueError, match="top_p"):
            sched.submit(p, 4, top_p=0.0)
        with pytest.raises(ValueError, match="top_p"):
            sched.submit(p, 4, top_p=1.5)
        # a greedy request decoding NEXT TO a sampling request stays
        # bitwise greedy (per-row where() on the choice)
        g = sched.submit(p, 8)
        s = sched.submit(p, 8, temperature=1.2, top_p=0.8, seed=5)
        greedy_out = np.asarray(g.result(timeout=120))
        s.result(timeout=120)
    assert np.array_equal(greedy_out, solo_oracle(m, m.params, p, 8))


@pytest.mark.slow
def test_sampling_skips_speculative_fast_path():
    """The draft-propose/verify acceptance rule is argmax-match —
    a sampling request must ride the normal bucketed step even when it
    is alone with a draft model armed (an all-sampled group is a spec
    FALLBACK, counted so operators see speculation going unused)."""
    m = shared_model()
    draft = _model(num_layers=1, pos_encoding="rope", num_kv_heads=2)
    p = np.asarray([3, 1, 4, 1, 5], np.int32)
    kw = dict(temperature=0.9, top_p=0.9, seed=31)
    want = _one(m, p, max_new=8, **kw)
    with _sched(m, draft_model=draft) as sched:
        out = np.asarray(sched.submit(p, 8, **kw).result(timeout=120))
        st = sched.stats()
    assert st["spec_rounds"] == 0, "sampling must not take the spec path"
    assert st["spec_fallbacks"] > 0, \
        "an all-sampled group with a draft armed is a counted fallback"
    assert np.array_equal(out, want), \
        "tokens identical with or without a draft model armed"


@pytest.mark.slow
def test_concurrent_submitters():
    """Thread-safety of submit(): many client threads, every result
    bitwise (the closed-loop bench shape at test scale)."""
    m = shared_model()
    rng = np.random.RandomState(12)
    plans = [(rng.randint(1, V, size=int(rng.randint(3, 20))),
              int(rng.randint(2, 8))) for _ in range(8)]
    results = [None] * len(plans)
    with _sched(m) as sched:
        def client(i):
            p, mn = plans[i]
            results[i] = sched.submit(p, mn).result(timeout=120)
        ts = [threading.Thread(target=client, args=(i,))
              for i in range(len(plans))]
        [t.start() for t in ts]
        [t.join() for t in ts]
        st = sched.stats()
    for i, (p, mn) in enumerate(plans):
        assert np.array_equal(results[i], solo_oracle(m, m.params, p, mn))
    assert st["completed"] == len(plans)
    _no_leaked_blocks(st)
