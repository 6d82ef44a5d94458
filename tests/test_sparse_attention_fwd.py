"""``dsa_fwd``'s one mask select on hand-made bitmasks, in the Pallas
interpreter against plain ``jax.numpy``.

The forward kernel masks a tile's scores once: a row's probabilities off
the selection are ``exp(NEG_INF - m)``, 0 once the row has met a selected
key. A row that crosses visited tiles before its first selected key sums
``exp(0)`` over them, which that key's ``alpha = 0`` wipes; a row that
never meets one is written as 0. Both are built here by hand, since
``dsa_select`` gives every row at least one key."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.kernels import sparse_attention as sa
from bigdl_tpu.kernels.flash_attention import NEG_INF
from test_sparse_attention import H, KV, SCALE, _attention, _inputs, _unpack

TILINGS = [(512, 256, 128), (1024, 256, 256)]
TILING_IDS = ["t512_256x128", "t1024_256x256"]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _pack(chosen, bq):
    """``[N, T, T]`` booleans as the bitmask ``dsa_select`` writes: bit
    ``r`` of word ``(i * R + w, s)`` is the pair (``i * bq + r * R + w``,
    ``s``)."""
    c = np.asarray(chosen)
    n, t, _ = c.shape
    r = bq // 32
    c = c.reshape(n, t // bq, 32, r, t).astype(np.uint32)
    words = np.sum(c << np.arange(32, dtype=np.uint32)[:, None, None],
                   axis=2, dtype=np.uint32)
    return jnp.asarray(words.reshape(n, t // bq * r, t).view(np.int32))


def _late_rows(t, bq, bk, seed=0):
    """Odd rows select keys of their own last causal key block only; even
    rows select keys before their query block (the first block's, where
    the query block is the first), so every causal tile is visited and the
    odd rows cross visited tiles with no key of theirs before their
    first."""
    rng = np.random.default_rng(seed)
    rows = np.arange(t)[:, None]
    cols = np.arange(t)[None, :]
    last = cols >= rows // bk * bk
    early = cols < np.maximum(rows // bq * bq, bk)
    allowed = np.where(rows % 2 == 1, last, early) & (cols <= rows)
    chosen = allowed & (rng.random((t, t)) < 0.5)
    # at least one key a row: the odd rows' own, the even rows' first
    chosen[np.arange(1, t, 2), np.arange(1, t, 2)] = True
    chosen[np.arange(0, t, 2), 0] = True
    return chosen[None]


@pytest.mark.parametrize("tiling", TILINGS, ids=TILING_IDS)
def test_pack_is_the_inverse_of_unpack(tiling):
    t, bq, bk = tiling
    chosen = _late_rows(t, bq, bk)
    np.testing.assert_array_equal(_unpack(_pack(chosen, bq), t, bq), chosen)


@pytest.mark.parametrize("tiling", TILINGS, ids=TILING_IDS)
def test_rows_whose_first_key_comes_late_are_the_reference(tiling):
    t, bq, bk = tiling
    x = _inputs(seed=5, t=t)
    chosen = _late_rows(t, bq, bk)
    bits = _pack(chosen, bq)
    # every causal tile holds a selected pair: no tile is skipped
    assert float(sa.tiles_visited(bits, bq, bk)) == 1.0
    o, lse = sa.dsa_attention(x["q"], x["k"], x["v"], bits, H, KV, SCALE,
                              bq, bk, interpret=True)
    ro, rlse, _ = _attention(x["q"], x["k"], x["v"], jnp.asarray(chosen))
    np.testing.assert_allclose(o, ro, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, rlse, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tiling", TILINGS, ids=TILING_IDS)
def test_gradients_of_rows_whose_first_key_comes_late(tiling):
    t, bq, bk = tiling
    x = _inputs(seed=6, t=t)
    chosen = _late_rows(t, bq, bk, seed=1)
    bits = _pack(chosen, bq)
    g = jax.random.normal(jax.random.PRNGKey(7), x["q"].shape)
    got = jax.grad(lambda q, k, v: jnp.sum(sa.dsa_attention(
        q, k, v, bits, H, KV, SCALE, bq, bk, interpret=True)[0] * g),
        (0, 1, 2))(x["q"], x["k"], x["v"])
    want = jax.grad(lambda q, k, v: jnp.sum(_attention(
        q, k, v, jnp.asarray(chosen))[0] * g), (0, 1, 2))(
        x["q"], x["k"], x["v"])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5,
                                   atol=2e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("tiling", TILINGS, ids=TILING_IDS)
def test_a_row_with_no_selected_key_reads_zero(tiling):
    """One row of the first query block and one of the last select
    nothing, inside query blocks whose tiles are all visited: their ``o``
    is exactly 0 and their ``lse`` ``NEG_INF``; every other row is the
    reference's."""
    t, bq, bk = tiling
    x = _inputs(seed=8, t=t)
    chosen = _late_rows(t, bq, bk, seed=2)
    empty = [5, t - 3]
    chosen[0, empty] = False
    bits = _pack(chosen, bq)
    o, lse = sa.dsa_attention(x["q"], x["k"], x["v"], bits, H, KV, SCALE,
                              bq, bk, interpret=True)
    o, lse = np.asarray(o), np.asarray(lse)
    assert np.all(o[0, empty] == 0.0)
    np.testing.assert_array_equal(lse[0][:, empty], np.float32(NEG_INF))
    live = np.setdiff1d(np.arange(t), empty)
    ro, rlse, _ = _attention(x["q"], x["k"], x["v"], jnp.asarray(chosen))
    np.testing.assert_allclose(o[0, live], np.asarray(ro)[0, live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse[0][:, live], np.asarray(rlse)[0][:, live],
                               rtol=2e-5, atol=2e-5)
