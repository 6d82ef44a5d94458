"""Flash attention dispatch: custom Pallas kernel on TPU, einsum elsewhere.

The kernels themselves live in ``bigdl_tpu.kernels`` (hand-written
Pallas; ``flash_attention`` for training/prefill, ``paged_attention``
for the serving tier's paged decode). This module is only the
dispatcher. Flash attention has its entries by the layout the caller
holds: :func:`flash_attention_qkv` for the fused projection ``[B, T,
3*H*D]`` of self-attention (the training step: no q, k or v array is
made), :func:`flash_attention_rows` for separate ``[B, T, H*D]`` operands
(nothing is copied around the kernels) and :func:`flash_attention` for
split heads ``[B, H, T, D]`` (KV caches, sequence-parallel blocks). For
every entry:

* on the ``tpu`` platform the compiled kernels run, and a kernel that
  fails to trace, lower or compile RAISES — there is no path from a
  refused kernel to the reference under the kernel's name;
* ``BIGDL_TPU_FLASH=interpret`` / ``BIGDL_TPU_PAGED_ATTN=interpret``
  force the same kernels through the Pallas interpreter (how the CPU
  test suite exercises the kernel code);
* any other platform selects the reference einsum / dense-gather paths
  in ``nn.attention`` (the CPU tests' path and the kernels' oracle), and
  ``BIGDL_TPU_FLASH=off`` / ``BIGDL_TPU_PAGED_ATTN=off`` asks for them
  explicitly. The selection is logged once per platform.
"""
from __future__ import annotations

import contextlib
import contextvars
import logging
import math
import os

import jax
import jax.numpy as jnp

logger = logging.getLogger("bigdl_tpu")
_warned = set()


def _warn_once(key, msg, *args):
    if key not in _warned:
        _warned.add(key)
        logger.warning(msg, *args)


@contextlib.contextmanager
def _scoped(var, value):
    """Set a trace-time context variable for the body of a ``with``."""
    tok = var.set(value)
    try:
        yield
    finally:
        var.reset(tok)


def _causal_bias(t):
    import numpy as np
    return jnp.where(np.tril(np.ones((t, t), np.bool_))[None, None],
                     0.0, -1e9)


def _einsum_attention(q, k, v, causal):
    from ..nn.attention import dot_product_attention
    mask = _causal_bias(q.shape[-2]) if causal else None
    return dot_product_attention(q, k, v, mask)


def _einsum_attention_rows(q, k, v, heads, causal, scale=None):
    """:func:`_einsum_attention` read from the ``[B, T, H, D]`` view of
    ``[B, T, H * D]`` operands: no head is moved."""
    b, t, c = q.shape
    q, k, v = (x.reshape(b, x.shape[1], heads, -1) for x in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    logits = (logits / math.sqrt(q.shape[-1]) if scale is None
              else logits * scale)
    if causal:
        logits = logits + _causal_bias(t)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, c)


def flash_mode() -> str:
    """Resolved dispatch mode: 'pallas' | 'interpret' | 'einsum'.

    The ONE policy decision shared by every flash consumer (this
    dispatcher and parallel/ring_flash.py): BIGDL_TPU_FLASH=off forces
    einsum, =interpret runs the Pallas kernels in the interpreter, and
    otherwise the ``tpu`` platform gets the compiled kernels."""
    mode = os.environ.get("BIGDL_TPU_FLASH", "auto")
    if mode == "off":
        return "einsum"
    if mode == "interpret":
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "einsum"


def _flash_blocks():
    """Kernel tile-size overrides for on-chip sweeps (trace-time env, like
    BIGDL_TPU_FUSED_BLOCK_*): BIGDL_TPU_FLASH_BLOCK_Q / _K."""
    return {"block_q": int(os.environ.get("BIGDL_TPU_FLASH_BLOCK_Q", 512)),
            "block_k": int(os.environ.get("BIGDL_TPU_FLASH_BLOCK_K", 512))}


def _dispatch(name, kernel_fn, einsum_fn):
    """The ONE dispatch policy (off / interpret / pallas / einsum) shared
    by every flash entry point. ``kernel_fn(interpret)`` runs the Pallas
    kernel — its failure propagates; ``einsum_fn()`` is the reference
    path a non-TPU platform (or ``=off``) selects."""
    mode = flash_mode()
    if mode != "einsum":
        return kernel_fn(mode == "interpret")
    if os.environ.get("BIGDL_TPU_FLASH") != "off":
        backend = jax.default_backend()
        _warn_once((name, "backend", backend),
                   "%s: platform %r uses the einsum path (set "
                   "BIGDL_TPU_FLASH=interpret to run the Pallas kernel in "
                   "interpreter mode)", name, backend)
    return einsum_fn()


# Trace-time training context: a data-parallel step whose batch dim jit
# partitions AUTOMATICALLY (DistriOptimizer's replicated mode) sets (mesh,
# batch axis) around its trace. Mosaic kernels cannot be partitioned
# automatically ("Please wrap the call in a shard_map" — the TPU v5e's
# answer to the first four-chip run), so the dispatcher shard_maps the
# kernel over the batch dim; attention is batch-local, so that is exact.
# Steps that are already an explicit shard_map (ZeRO-1, the per-layer
# sparse wire) never set it.
_DATA_CTX = contextvars.ContextVar("bigdl_tpu_flash_data_ctx",
                                   default=(None, None))


def data_parallel_context(mesh, axis: str = "data"):
    """Trace-time context: the (B, H, T, D) operands of every
    :func:`flash_attention` traced inside are batch-sharded over
    ``axis`` of ``mesh`` by jit's automatic partitioning."""
    return _scoped(_DATA_CTX, (mesh, axis))


def _kernel_obs(counter: str):
    """Trace-time dispatch accounting: one bump per kernel call (flash) or
    program (paged) BUILT on each path (execution never re-enters Python,
    so what was built is the honest unit — serve/decode_steps counts the
    dispatches riding a paged program)."""
    from .. import observability as obs
    if obs.enabled():
        obs.counter(f"kernels/{counter}").inc()


def _over_batch(fused, operands=3):
    """``fused`` under the :func:`data_parallel_context`'s ``shard_map``,
    if one is set; the batch is the leading dimension of each of its
    ``operands`` in every layout."""
    mesh, axis = _DATA_CTX.get()
    if mesh is None:
        return fused
    from jax.sharding import PartitionSpec as P
    from ..utils.compat import shard_map
    return shard_map(fused, mesh=mesh, in_specs=(P(axis),) * operands,
                     out_specs=P(axis), check_vma=False)


def flash_attention(q, k, v, causal: bool = False):
    """q, k, v: (B, H, T, D) — split heads, as a decode cache and the
    sequence-parallel exchanges hold them. A caller whose operands are
    ``[B, T, H * D]`` uses :func:`flash_attention_rows`."""

    def kernel(interpret):
        # imported lazily: einsum-path callers never load pallas
        from ..kernels.flash_attention import flash_attention_fused
        _kernel_obs("flash_heads")
        return _over_batch(lambda q, k, v: flash_attention_fused(
            q, k, v, causal=causal, interpret=interpret,
            **_flash_blocks()))(q, k, v)

    return _dispatch("flash attention", kernel,
                     lambda: _einsum_attention(q, k, v, causal))


def flash_attention_rows(q, k, v, num_heads: int, causal: bool = False,
                         scale: float = None):
    """q, k, v: (B, T, H*D), the q/k/v projections as they are written;
    returns (B, T, H*D), what the output projection reads. Where the
    kernels can index that layout (whole heads fill 128-lane blocks:
    ``kernels.flash_attention.heads_per_block``) no head is split or
    merged; other head sizes are split here, go through
    :func:`flash_attention` and are merged again. ``scale``: the softmax
    scale where it is not ``D ** -0.5`` (rows layout only)."""
    b, t, c = q.shape
    if flash_mode() != "einsum":
        from ..kernels.flash_attention import heads_per_block
        if heads_per_block(num_heads, c // num_heads) is None:
            if scale is not None:
                raise NotImplementedError(
                    "a softmax scale of its own needs heads that fill "
                    "128-lane blocks")
            split = lambda x: x.reshape(b, x.shape[1], num_heads,  # noqa: E731
                                        -1).swapaxes(1, 2)
            o = flash_attention(split(q), split(k), split(v), causal=causal)
            return o.swapaxes(1, 2).reshape(b, t, c)

    def kernel(interpret):
        from ..kernels.flash_attention import flash_attention_rows as rows
        _kernel_obs("flash_rows")
        return _over_batch(lambda q, k, v: rows(
            q, k, v, num_heads, causal=causal, scale=scale,
            interpret=interpret, **_flash_blocks()))(q, k, v)

    return _dispatch("flash attention", kernel,
                     lambda: _einsum_attention_rows(q, k, v, num_heads,
                                                    causal, scale))


def flash_attention_qkv(qkv, num_heads: int, causal: bool = False):
    """:func:`flash_attention_rows` of self-attention whose q, k and v are
    one matmul's output: qkv (B, T, 3*H*D), q's lanes, then k's, then
    v's; returns (B, T, H*D). Where the kernels can index the layout they
    read the three out of ``qkv`` themselves and no q, k or v array is
    made; the einsum path and head sizes that fill no 128-lane block
    slice it and go the way of separate operands."""
    c = qkv.shape[-1] // 3
    sliced = lambda: tuple(qkv[..., i * c:(i + 1) * c]  # noqa: E731
                           for i in range(3))
    from ..kernels.flash_attention import heads_per_block
    if heads_per_block(num_heads, c // num_heads) is None:
        return flash_attention_rows(*sliced(), num_heads, causal=causal)

    def kernel(interpret):
        from ..kernels.flash_attention import flash_attention_qkv as fused
        _kernel_obs("flash_qkv")
        return _over_batch(lambda qkv: fused(
            qkv, num_heads, causal=causal, interpret=interpret,
            **_flash_blocks()), operands=1)(qkv)

    return _dispatch("flash attention", kernel,
                     lambda: _einsum_attention_rows(*sliced(), num_heads,
                                                    causal))


def _einsum_chunk_attention(q, k, v, q_offset, kv_len):
    from ..nn.attention import dot_product_attention
    k, v = k[:, :, :kv_len], v[:, :, :kv_len]
    s = q.shape[-2]
    mask = jnp.where(
        jnp.arange(kv_len)[None, :] <= q_offset + jnp.arange(s)[:, None],
        0.0, -1e9)[None, None]
    return dot_product_attention(q, k, v, mask)


def flash_chunk_attention(q, k, v, q_offset: int, kv_len: int = None):
    """Rectangular-causal chunk attention over the first ``kv_len``
    positions of a KV cache (Transformer.prefill_chunked):
    q (B, H, S, D) at global positions q_offset... Same dispatch policy
    as :func:`flash_attention`; the einsum path materialises the
    (S, kv_len) mask/logits the kernel exists to avoid."""
    if kv_len is None:
        kv_len = k.shape[2]

    def kernel(interpret):
        from ..kernels.flash_attention import flash_chunk_attention as fck
        return fck(q, k, v, q_offset, kv_len=kv_len, interpret=interpret,
                   **_flash_blocks())

    return _dispatch("chunk attention", kernel,
                     lambda: _einsum_chunk_attention(q, k, v, q_offset,
                                                    kv_len))


# ---------------------------------------------------------------------------
# paged decode attention (serving tier)
# ---------------------------------------------------------------------------

# Trace-time serving context: the DecodeScheduler's compiled step sets
# (mesh, kv-head shard axis) around its trace so the dispatch below can
# shard_map the kernel per kv-head group under TP serving. A contextvar
# (not a model attribute) keeps shared model objects placement-free —
# two schedulers serving the same model at different placements never
# see each other's mesh.
_PAGED_CTX = contextvars.ContextVar("bigdl_tpu_paged_ctx",
                                    default=(None, None))


def paged_serving_context(mesh=None, shard_axis=None):
    """Trace-time context: set by the serving step around its
    ``decode_paged`` trace. ``shard_axis``: mesh axis the KV pages'
    kv-head dim is sharded over (None = pages replicated)."""
    return _scoped(_PAGED_CTX, (mesh, shard_axis))


def paged_mode() -> str:
    """Resolved paged-decode dispatch mode: 'pallas' | 'interpret' |
    'dense'. Same policy shape as :func:`flash_mode`, gated by its own
    env knob (``BIGDL_TPU_PAGED_ATTN`` = auto/on/off/interpret) so the
    serving kernel can be A/B'd independently of the training kernels.
    The dense gather path is what a non-TPU platform selects AND the
    kernel's oracle."""
    mode = os.environ.get("BIGDL_TPU_PAGED_ATTN", "auto")
    if mode == "off":
        return "dense"
    if mode == "interpret":
        return "interpret"
    if mode == "on":
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "dense"


def paged_attention(q, k_pages, v_pages, block_tables, positions,
                    dense_fn):
    """The serving tier's paged-decode attention seam.

    q: (B, nH, S, D); k_pages/v_pages: (num_blocks, kvH, block_size, D)
    ALREADY holding this chunk's scattered K/V; block_tables:
    (B, max_blocks) int32; positions: (B,) int32. ``dense_fn()`` is the
    caller's gathered-view einsum — the non-TPU path and the oracle.

    Under a :func:`paged_serving_context` mesh the kernel runs inside
    ``shard_map`` per kv-head group: attention is head-local, so a
    kvH-sharded page pool needs no cross-shard communication — each
    shard streams its own heads' blocks. Pages replicated on the mesh
    (FSDP placement, or kvH not divisible by the axis) shard_map with
    replicated specs instead. A kernel failure raises."""
    mode = paged_mode()
    if mode == "dense":
        if os.environ.get("BIGDL_TPU_PAGED_ATTN") != "off":
            backend = jax.default_backend()
            _warn_once(("paged attention", "backend", backend),
                       "paged attention: platform %r uses the dense "
                       "gather path (set BIGDL_TPU_PAGED_ATTN=interpret to "
                       "run the Pallas kernel in interpreter mode)", backend)
        _kernel_obs("paged_attn_dense_programs")
        return dense_fn()
    interpret = mode == "interpret"
    mesh, axis = _PAGED_CTX.get()
    from ..kernels.paged_attention import paged_decode_attention
    if mesh is None:
        out = paged_decode_attention(q, k_pages, v_pages, block_tables,
                                     positions, interpret=interpret)
    else:
        from jax.sharding import PartitionSpec as P
        from ..utils.compat import shard_map
        head = P(None, axis) if axis else P()

        def body(q, kp, vp, tbl, pos):
            return paged_decode_attention(
                q, kp, vp, tbl, pos, interpret=interpret,
                vma={axis} if axis else None)

        out = shard_map(body, mesh=mesh,
                        in_specs=(head, head, head, P(), P()),
                        out_specs=head, check_vma=False)(
            q, k_pages, v_pages, block_tables, positions)
    _kernel_obs("paged_attn_programs")
    return out
