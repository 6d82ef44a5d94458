"""Pallas paged-attention decode kernel (ISSUE 11) — interpret mode.

Kernel discipline (kernels/flash_attention.py's): the dense
``Attention._paged_gather_attend`` einsum is the ORACLE — the kernel
must match it to ulps on logits and bitwise on greedy argmax across the
serving shapes (S=1 decode, S>1 chunked prefill / speculative verify,
GQA and MHA, scattered tables, null-table padded slots). The dispatch
seam (``parallel.flash.paged_attention``) is gated by
``BIGDL_TPU_PAGED_ATTN`` with the dense path as fallback; the
trace-count spy proves which path built the program.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.kernels import paged_attention as pk
from bigdl_tpu.parallel import flash as pf


def _dense_ref(q, kp, vp, tables, pos):
    """The gathered-view einsum, standalone (mirrors
    Attention._paged_gather_attend for arbitrary head counts)."""
    B, nH, S, D = q.shape
    kvH, bs = kp.shape[1], kp.shape[2]
    G = nH // kvH
    kg = jnp.moveaxis(kp[tables], 2, 1)
    vg = jnp.moveaxis(vp[tables], 2, 1)
    t = tables.shape[1] * bs
    kg = kg.reshape(B, kvH, t, D)
    vg = vg.reshape(B, kvH, t, D)
    pos_s = pos[:, None] + jnp.arange(S)[None, :]
    keep = (jnp.arange(t)[None, None, :] <= pos_s[:, :, None])
    if G > 1:
        qg = q.reshape(B, kvH, G, S, D)
        logits = jnp.einsum("bkgsd,bktd->bkgst", qg, kg) / math.sqrt(D)
        logits = jnp.where(keep[:, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bkgst,bktd->bkgsd", w, vg).reshape(B, nH, S, D)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, kg) / math.sqrt(D)
    logits = jnp.where(keep[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", w, vg)


def _case(rng, B, nH, kvH, S, D, bs, nblk):
    NB = 1 + B * nblk
    kp = jnp.asarray(rng.randn(NB, kvH, bs, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(NB, kvH, bs, D).astype(np.float32))
    tables = np.zeros((B, nblk), np.int32)
    for b in range(B):
        tables[b] = rng.permutation(np.arange(1, NB))[:nblk]
    pos = rng.randint(0, nblk * bs - S, size=B).astype(np.int32)
    q = jnp.asarray(rng.randn(B, nH, S, D).astype(np.float32))
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos)


@pytest.mark.parametrize("B,nH,kvH,S,D,bs,nblk", [
    (3, 4, 2, 1, 8, 4, 6),    # GQA decode step
    (2, 4, 4, 1, 16, 8, 4),   # MHA decode step
    (2, 4, 2, 8, 8, 4, 8),    # chunked prefill (S = chunk)
    (1, 8, 2, 5, 64, 16, 4),  # speculative verify (S = k+1), wide head
])
def test_kernel_matches_dense_oracle_ulp(B, nH, kvH, S, D, bs, nblk):
    rng = np.random.RandomState(hash((B, nH, S)) % 2**31)
    q, kp, vp, tables, pos = _case(rng, B, nH, kvH, S, D, bs, nblk)
    want = _dense_ref(q, kp, vp, tables, pos)
    got = pk.paged_decode_attention(q, kp, vp, tables, pos,
                                    interpret=True)
    err = float(jnp.max(jnp.abs(want - got)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= 4e-6 * max(scale, 1.0), (err, scale)


def test_kernel_null_table_padded_slot_no_nan():
    """A padded slot (null table, pos 0) must produce finite output —
    its rows are garbage the scheduler never reads, but a NaN would
    poison the whole batch through the shared program."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 4, 1, 8).astype(np.float32))
    kp = jnp.asarray(rng.randn(5, 2, 4, 8).astype(np.float32))
    vp = jnp.asarray(rng.randn(5, 2, 4, 8).astype(np.float32))
    tables = jnp.asarray(np.array([[1, 2, 0], [0, 0, 0]], np.int32))
    pos = jnp.asarray(np.array([6, 0], np.int32))
    out = pk.paged_decode_attention(q, kp, vp, tables, pos,
                                    interpret=True)
    assert bool(jnp.isfinite(out).all())
    want = _dense_ref(q, kp, vp, tables, pos)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5


def test_kernel_greedy_argmax_bitwise_through_projection():
    """The serving gate in miniature: project kernel/dense attention
    outputs through a vocab head — greedy argmax must agree exactly
    (the online-softmax ulps never flip a token)."""
    rng = np.random.RandomState(3)
    q, kp, vp, tables, pos = _case(rng, 4, 4, 2, 1, 16, 8, 6)
    wo = jnp.asarray(rng.randn(4 * 16, 48).astype(np.float32))
    dense = _dense_ref(q, kp, vp, tables, pos)
    kern = pk.paged_decode_attention(q, kp, vp, tables, pos,
                                     interpret=True)
    to_logits = lambda o: o.transpose(0, 2, 1, 3).reshape(4, 1, -1) @ wo
    assert np.array_equal(
        np.asarray(jnp.argmax(to_logits(dense), -1)),
        np.asarray(jnp.argmax(to_logits(kern), -1)))


def test_dispatch_gating_and_trace_spy(monkeypatch):
    """BIGDL_TPU_PAGED_ATTN routes the seam: off/auto-on-CPU -> dense
    (no kernel trace), interpret -> kernel (trace count bumps); a
    kernel failure RAISES — it never returns the dense value under the
    kernel's name."""
    rng = np.random.RandomState(1)
    q, kp, vp, tables, pos = _case(rng, 2, 4, 2, 1, 8, 4, 4)
    dense = lambda: _dense_ref(q, kp, vp, tables, pos)
    want = dense()

    monkeypatch.setenv("BIGDL_TPU_PAGED_ATTN", "off")
    t0 = pk.trace_count()
    out = pf.paged_attention(q, kp, vp, tables, pos, dense)
    assert pk.trace_count() == t0
    assert np.array_equal(np.asarray(out), np.asarray(want))

    monkeypatch.delenv("BIGDL_TPU_PAGED_ATTN", raising=False)
    out = pf.paged_attention(q, kp, vp, tables, pos, dense)   # auto=dense on CPU
    assert pk.trace_count() == t0

    monkeypatch.setenv("BIGDL_TPU_PAGED_ATTN", "interpret")
    out = pf.paged_attention(q, kp, vp, tables, pos, dense)
    assert pk.trace_count() == t0 + 1, "spy: the Pallas path must trace"
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5

    # no fallback: with the mode resolved to the kernel ('on' is what the
    # tpu platform resolves to), a kernel that throws propagates
    def boom(*a, **kw):
        raise RuntimeError("injected kernel failure")
    monkeypatch.setattr(pk, "paged_decode_attention", boom)
    dense_calls = []
    for mode in ("on", "interpret"):
        monkeypatch.setenv("BIGDL_TPU_PAGED_ATTN", mode)
        with pytest.raises(RuntimeError, match="injected kernel failure"):
            pf.paged_attention(q, kp, vp, tables, pos,
                               lambda: dense_calls.append(1) or want)
    assert not dense_calls, "the dense path must not run behind a failure"


def test_dispatch_counters_exported(monkeypatch):
    from bigdl_tpu import observability as obs
    obs.enable()
    try:
        rng = np.random.RandomState(2)
        q, kp, vp, tables, pos = _case(rng, 2, 4, 2, 1, 8, 4, 4)
        dense = lambda: _dense_ref(q, kp, vp, tables, pos)
        monkeypatch.setenv("BIGDL_TPU_PAGED_ATTN", "interpret")
        pf.paged_attention(q, kp, vp, tables, pos, dense)
        assert obs.registry().get("kernels/paged_attn_programs").value >= 1
        monkeypatch.setenv("BIGDL_TPU_PAGED_ATTN", "off")
        pf.paged_attention(q, kp, vp, tables, pos, dense)
        assert obs.registry().get(
            "kernels/paged_attn_dense_programs").value >= 1
    finally:
        obs.disable()


def test_kernel_under_jit_compiles_once_per_shape():
    rng = np.random.RandomState(4)
    q, kp, vp, tables, pos = _case(rng, 2, 4, 2, 1, 8, 4, 4)
    f = jax.jit(lambda *a: pk.paged_decode_attention(*a, interpret=True))
    t0 = pk.trace_count()
    a = f(q, kp, vp, tables, pos)
    b = f(q, kp, vp, tables, pos + 1)   # same shapes -> no re-trace
    assert pk.trace_count() == t0 + 1
    assert a.shape == b.shape == q.shape
