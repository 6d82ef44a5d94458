"""The learned-sparse-attention kernels (``kernels/sparse_attention.py``) in
the Pallas interpreter against plain ``jax.numpy``: the exact top-k
selection and its tie rule, the sparse grouped-query attention forward and
backward, the indexer's KL and its gradient, the tiles skipped, and the
grid steps that fetch nothing (their index maps walked in pure Python)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.kernels import sparse_attention as sa

N, T, H, KV, D, HI, DI, K = 1, 512, 4, 2, 128, 4, 64, 64
BQ, BK = 256, 128
SCALE = D ** -0.5


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(seed=0, t=T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        q=jax.random.normal(ks[0], (N, t, H * D)),
        k=jax.random.normal(ks[1], (N, t, KV * D)),
        v=jax.random.normal(ks[2], (N, t, KV * D)),
        qi=jax.random.normal(ks[3], (N, HI, t, DI)),
        kit=jax.random.normal(ks[4], (N, DI, t)),
        w=0.1 * jax.random.normal(ks[5], (N, HI, t)))


def _scores(qi, kit, w):
    """``I [N, T, T]`` in plain jax.numpy."""
    a = jnp.einsum("nhtd,nds->nhts", qi, kit)
    return jnp.sum(jnp.maximum(a, 0.0) * w[..., None], axis=1)


def _chosen(index, topk):
    """The reference's selection: ``lax.top_k`` of each causal row (ties to
    the lower index)."""
    t = index.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, top = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), min(topk, t))
    hit = jnp.zeros(index.shape, bool).at[
        jnp.arange(index.shape[0])[:, None, None],
        jnp.arange(t)[None, :, None], top].set(True)
    return hit & causal


def _unpack(bits, t, bq=BQ):
    """The bitmask ``[N, T // bq * R, T]`` as ``[N, T, T]`` booleans."""
    b, r = np.asarray(bits), bq // 32
    out = np.zeros((b.shape[0], t, t), bool)
    for i in range(t // bq):
        for bit in range(32):
            out[:, i * bq + bit * r:i * bq + (bit + 1) * r] = (
                (b[:, i * r:(i + 1) * r] >> bit) & 1) != 0
    return out


def _attention(q, k, v, chosen):
    """Grouped-query attention over the chosen pairs: o and lse."""
    t = q.shape[1]
    qh = q.reshape(N, t, KV, H // KV, D)
    s = jnp.einsum("ntkgd,nskd->nkgts", qh, k.reshape(N, t, KV, D)) * SCALE
    s = jnp.where(chosen[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgts,nskd->ntkgd", p, v.reshape(N, t, KV, D))
    return (o.reshape(N, t, H * D),
            jax.scipy.special.logsumexp(s, axis=-1).reshape(N, H, t), p)


def _select(x, topk=K):
    return sa.dsa_select(x["qi"], x["kit"], x["w"], topk, BQ, BK,
                         interpret=True)


@pytest.fixture(scope="module")
def x():
    return _inputs()


@pytest.fixture(scope="module")
def selected(x):
    return _select(x)


@pytest.fixture(scope="module", params=[(T, BQ, BK), (1024, 256, 256)],
                ids=["skips_in_one_row", "skips_span_rows"])
def case(request):
    """Inputs, their selection and the tiling: at ``T`` = 512 with 256 x
    128 tiles only the first query row has non-causal steps; at 1,024 with
    256 x 256 tiles three rows have them, each kernel's skipped steps run
    over several rows and the clamped index maps reach back across them."""
    t, bq, bk = request.param
    x = _inputs(t=t)
    bits, ilse = sa.dsa_select(x["qi"], x["kit"], x["w"], K, bq, bk,
                               interpret=True)
    return dict(x=x, bits=bits, ilse=ilse, t=t, bq=bq, bk=bk)


def test_the_selection_is_the_exact_top_k_of_every_row(x, selected):
    bits, ilse = selected
    index = _scores(x["qi"], x["kit"], x["w"])
    want = np.asarray(_chosen(index, K))
    got = _unpack(bits, T)
    np.testing.assert_array_equal(got, want)
    counts = got.sum(-1)[0]
    np.testing.assert_array_equal(counts, np.minimum(np.arange(T) + 1, K))
    ref = jax.scipy.special.logsumexp(jnp.where(want, index, -jnp.inf), -1)
    np.testing.assert_allclose(ilse[:, 0], ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["all_tied", "two_values", "zero_signs"])
def test_ties_go_to_the_earlier_key(x, kind):
    """Scores with ties at the threshold: the earliest keys among them are
    the ones taken, as ``lax.top_k`` takes them; -0 ties with +0."""
    y = dict(x)
    if kind == "all_tied":
        y["qi"] = jnp.zeros_like(x["qi"])
    elif kind == "two_values":
        y["kit"] = jnp.where(jnp.arange(T) % 3 == 0, 1.0, -1.0)[
            None, None] * jnp.ones((N, DI, T))
        y["qi"] = jnp.abs(x["qi"])
        y["w"] = jnp.abs(x["w"])
    else:
        # w < 0 on every head: relu's zeros become -0 on some pairs
        y["w"] = -jnp.abs(x["w"])
    bits, _ = _select(y)
    want = np.asarray(_chosen(_scores(y["qi"], y["kit"], y["w"]), K))
    np.testing.assert_array_equal(_unpack(bits, T), want)
    if kind == "all_tied":
        cols = np.arange(T)
        np.testing.assert_array_equal(
            want[0], (cols[None] <= cols[:, None]) & (cols[None] < K))


def test_the_sparse_attention_and_its_gradients_are_the_references(case):
    x, bits, bq, bk = case["x"], case["bits"], case["bq"], case["bk"]
    chosen = jnp.asarray(_unpack(bits, case["t"], bq))
    o, lse = sa.dsa_attention(x["q"], x["k"], x["v"], bits, H, KV, SCALE,
                              bq, bk, interpret=True)
    ro, rlse, _ = _attention(x["q"], x["k"], x["v"], chosen)
    np.testing.assert_allclose(o, ro, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, rlse, rtol=2e-5, atol=2e-5)
    g = jax.random.normal(jax.random.PRNGKey(9), o.shape)
    got = jax.grad(lambda q, k, v: jnp.sum(sa.dsa_attention(
        q, k, v, bits, H, KV, SCALE, bq, bk, interpret=True)[0] * g),
        (0, 1, 2))(x["q"], x["k"], x["v"])
    want = jax.grad(lambda q, k, v: jnp.sum(
        _attention(q, k, v, chosen)[0] * g), (0, 1, 2))(
        x["q"], x["k"], x["v"])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5,
                                   atol=2e-5 * float(jnp.max(jnp.abs(b))))


def test_the_indexers_kl_and_its_gradients_are_the_references(case):
    x, bits, ilse = case["x"], case["bits"], case["ilse"]
    bq, bk = case["bq"], case["bk"]
    chosen = jnp.asarray(_unpack(bits, case["t"], bq))
    _, lse = sa.dsa_attention(x["q"], x["k"], x["v"], bits, H, KV, SCALE,
                              bq, bk, interpret=True)
    _, _, p = _attention(x["q"], x["k"], x["v"], chosen)
    mean = jnp.mean(p, axis=(1, 2))

    def ref(qi, kit, w):
        log_q = jax.nn.log_softmax(
            jnp.where(chosen, _scores(qi, kit, w), -jnp.inf), -1)
        return jnp.sum(jnp.where(chosen & (mean > 0), mean * (
            jnp.log(jnp.where(mean > 0, mean, 1.0))
            - jnp.where(chosen, log_q, 0.0)), 0.0))

    def got(qi, kit, w):
        return sa.dsa_index_loss(qi, kit, w, ilse, bits, x["q"], x["k"], lse,
                                 H, KV, SCALE, bq, bk, interpret=True)

    args = (x["qi"], x["kit"], x["w"])
    kl, grads = jax.value_and_grad(got, (0, 1, 2))(*args)
    rkl, rgrads = jax.value_and_grad(ref, (0, 1, 2))(*args)
    np.testing.assert_allclose(kl, rkl, rtol=1e-5)
    for a, b in zip(grads, rgrads):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_rows_no_longer_than_topk_attend_to_every_earlier_key():
    """T <= topk: the selection is the causal triangle, and the sparse
    kernels are dense causal grouped-query attention."""
    x = _inputs(seed=3, t=256)
    bits, _ = sa.dsa_select(x["qi"], x["kit"], x["w"], 2048, BQ, BK,
                            interpret=True)
    causal = np.tril(np.ones((256, 256), bool))
    np.testing.assert_array_equal(_unpack(bits, 256)[0], causal)
    o, _ = sa.dsa_attention(x["q"], x["k"], x["v"], bits, H, KV, SCALE, BQ,
                            BK, interpret=True)
    qh = x["q"].reshape(N, 256, H, D)
    kh = jnp.repeat(x["k"].reshape(N, 256, KV, D), H // KV, axis=2)
    vh = jnp.repeat(x["v"].reshape(N, 256, KV, D), H // KV, axis=2)
    s = jnp.einsum("nthd,nshd->nhts", qh, kh) * SCALE
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    dense = jnp.einsum("nhts,nshd->nthd", p, vh).reshape(N, 256, H * D)
    np.testing.assert_allclose(o, dense, rtol=2e-5, atol=2e-5)


def test_tiles_without_a_selected_pair_are_skipped_and_counted(x):
    """All scores tied: every row takes the first ``K`` keys, which lie in
    the first key block; the other causal tiles hold nothing, are skipped,
    and the attention is still the reference's."""
    y = dict(x, qi=jnp.zeros_like(x["qi"]))
    bits, _ = _select(y)
    chosen = jnp.asarray(_unpack(bits, T))
    # causal tiles of 256 x 128 over 512: 2 + 4; the first key block only
    assert float(sa.tiles_visited(bits, BQ, BK)) == pytest.approx(2 / 6)
    o, _ = sa.dsa_attention(x["q"], x["k"], x["v"], bits, H, KV, SCALE, BQ,
                            BK, interpret=True)
    np.testing.assert_allclose(o, _attention(x["q"], x["k"], x["v"],
                                             chosen)[0], rtol=2e-5, atol=2e-5)


def test_block_and_length_rules():
    x = _inputs(t=384)
    with pytest.raises(ValueError, match="multiple of"):
        sa.dsa_select(x["qi"], x["kit"], x["w"], K, BQ, BK, interpret=True)
    with pytest.raises(ValueError, match="multiple of 256"):
        sa.dsa_select(x["qi"], x["kit"], x["w"], K, 128, BK, interpret=True)


# the cell keye_train_16k's shapes: one row of 16,384 positions, 32 query
# and 4 KV heads of 128, an indexer of 16 heads of 64, 512 x 512 tiles
SHAPES = {"test": dict(t=T, heads=H, kv=KV, hi=HI, bq=BQ, bk=BK),
          "keye_16k": dict(t=16384, heads=32, kv=4, hi=16, bq=512, bk=512)}
# the grid axes of each tiled kernel that hold the query block i and the
# key block j
GRID_IJ = {"dsa_fwd": (2, 3), "dsa_bwd_dq": (2, 3), "dsa_bwd_dkv": (3, 2),
           "dsa_index_bwd": (1, 2)}


def _calls(monkeypatch, t, heads, kv, hi, bq, bk):
    """Each tiled kernel's grid, input BlockSpecs and input dtypes, by
    ``name=``, as the attention's value and gradient and the indexer's loss
    build them at these shapes: ``pallas_call`` is replaced by a recorder
    under ``jax.eval_shape``, so nothing is allocated and no kernel runs."""
    calls = {}

    def record(kernel, *, grid, in_specs, out_shape, name, **_):
        def call(*args):
            calls[name] = (grid, in_specs, [a.dtype for a in args])
            return jax.tree.map(lambda o: jnp.zeros(o.shape, o.dtype),
                                out_shape)
        return call

    monkeypatch.setattr(sa.pl, "pallas_call", record)
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    shapes = (sds((N, t, heads * D), f32), sds((N, t, kv * D), f32),
              sds((N, t, kv * D), f32), sds((N, t // 32, t), jnp.int32),
              sds((N, hi, t, DI), f32), sds((N, DI, t), f32),
              sds((N, hi, t), f32), sds((N, 1, t), f32))

    def build(q, k, v, bits, qi, kit, w, ilse):
        grads = jax.grad(lambda q, k, v: jnp.sum(sa.dsa_attention(
            q, k, v, bits, heads, kv, SCALE, bq, bk)[0]), (0, 1, 2))(q, k, v)
        _, lse = sa.dsa_attention(q, k, v, bits, heads, kv, SCALE, bq, bk)
        return grads, sa.dsa_index_loss(qi, kit, w, ilse, bits, q, k, lse,
                                        heads, kv, SCALE, bq, bk)

    jax.eval_shape(build, *shapes)
    return calls


def _blocks(grid, spec):
    """The block index of ``spec`` at every grid step, in the pipeline's
    order (the last axis fastest): ``(steps, rank)``."""
    steps = np.indices(grid).reshape(len(grid), -1)
    index = spec.index_map(*steps)
    return np.stack([np.broadcast_to(np.asarray(c), steps.shape[1:])
                     for c in index], axis=-1)


def _fetched(blocks, specs, dtypes, steps):
    """Bytes the pipeline copies in over ``steps`` (a mask of the grid's
    steps): an input's block on the first step and wherever its index
    differs from the previous step's."""
    total = 0
    for b, spec, dtype in zip(blocks, specs, dtypes):
        b = b[steps]
        copies = 1 + int(np.sum(np.any(b[1:] != b[:-1], axis=-1)))
        total += copies * int(np.prod(spec.block_shape)) * dtype.itemsize
    return total


@pytest.mark.parametrize("shapes", sorted(SHAPES))
@pytest.mark.parametrize("kernel", sorted(GRID_IJ))
def test_non_causal_grid_steps_fetch_nothing(monkeypatch, kernel, shapes):
    """A step whose tile is not causal takes no block of its own: an
    input's block index stays the previous step's, or (on the first steps
    of a ``dsa_bwd_dkv`` row) moves to the block of the next causal step,
    which then fetches nothing. What a call fetches is what its causal
    steps alone would fetch."""
    s = SHAPES[shapes]
    grid, specs, dtypes = _calls(monkeypatch, **s)[kernel]
    steps = np.indices(grid).reshape(len(grid), -1)
    i, j = (steps[a] for a in GRID_IJ[kernel])
    causal = j * s["bk"] <= i * s["bq"] + s["bq"] - 1
    at = np.arange(causal.size)
    # the next causal step, in grid order, of every step
    ahead = np.minimum.accumulate(np.where(causal, at, at[-1])[::-1])[::-1]
    blocks = [_blocks(grid, spec) for spec in specs]
    for n, b in enumerate(blocks):
        moved = np.concatenate([[True], np.any(b[1:] != b[:-1], axis=-1)])
        early = moved & ~causal
        np.testing.assert_array_equal(b[early], b[ahead[early]],
                                      err_msg=f"{kernel} input {n}")
        assert not np.any(moved[ahead[early]]), (kernel, n)
    assert (_fetched(blocks, specs, dtypes, causal)
            == _fetched(blocks, specs, dtypes, np.ones_like(causal)))
    if shapes == "keye_16k":
        # 496 of the 1,024 tiles a KV head lie past the diagonal
        assert (int(np.sum(~causal)), causal.size) == (1984, 4096)
