"""Secondary BASELINE.json benchmark configs (run via ``python bench.py --all``).

Covers the four non-headline configs from BASELINE.json:
  * LeNet-5 / MNIST train (images/sec)
  * VGG-16 / CIFAR-10 train (images/sec)
  * LSTM language model / PTB-shape train (tokens/sec)
  * Inception-v1 int8 inference (images/sec, exercises the quantization path
    end-to-end: float model -> quantize() -> int8 forward)

Baseline constants are the reference's MKL/MKL-DNN Xeon-node estimates from
SURVEY §6 (the reference publishes no exact per-config numbers; these are
order-of-magnitude anchors recorded here as fixed constants so vs_baseline is
stable across rounds).

Each config runs in the bench.py process via :func:`bench_one`; the
platform check and the failed-config policy live in ``bench.py``'s main.
"""
from __future__ import annotations

import os
import time

# Xeon-node estimates (fixed anchors, see module docstring)
_BASE = {
    "lenet_mnist": 2000.0,       # images/sec train
    "vgg16_cifar10": 40.0,       # images/sec train
    "lstm_ptb": 8000.0,          # tokens/sec train
    "inception_v1_int8": 200.0,  # images/sec int8 inference
}


def _sized(on_tpu, tpu, cpu):
    return tpu if on_tpu else cpu


def _train_bench(model, crit, x, y, optim, steps, warmup, bf16=True,
                 bf16_inputs=False):
    """Functional jitted train loop over (params, opt_state, mstate).

    ``bf16`` casts f32 params to bf16 inside the step (f32 master params,
    bf16 MXU compute, f32 loss/update — the headline ResNet recipe;
    f32 matmuls run the MXU at a fraction of bf16 throughput).
    ``bf16_inputs`` additionally casts the input batch — only for
    image-valued inputs; token-INDEX inputs must stay exact (bf16 cannot
    represent integers above 256 exactly)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.utils.amp import bf16_params

    params, mstate = model.init(jax.random.PRNGKey(0))
    opt_state = optim.init_state(params)
    if bf16_inputs and x.dtype == jnp.float32:
        x = x.astype(jnp.bfloat16)

    def train_step(params, opt_state, mstate, x, y, lr):
        def loss_fn(p):
            if bf16:
                p = bf16_params(p)
            out, new_state = model.apply(p, mstate, x, training=True,
                                         rng=jax.random.PRNGKey(0))
            return crit._forward(out.astype(jnp.float32), y), new_state
        (loss, new_mstate), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt = optim.update(grads, params, opt_state, lr)
        return loss, new_params, new_opt, new_mstate

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    lr = jnp.float32(0.01)
    carry = [params, opt_state, mstate]
    for _ in range(warmup):
        loss, *carry = step(*carry, x, y, lr)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, *carry = step(*carry, x, y, lr)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert final == final, "NaN loss in bench"
    return dt


def bench_lenet(on_tpu):
    import numpy as np
    import jax.numpy as jnp
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD

    batch = _sized(on_tpu, 1024, 32)
    steps, warmup = _sized(on_tpu, 30, 2), _sized(on_tpu, 5, 1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 28, 28).astype(np.float32))
    y = jnp.asarray(rng.randint(1, 11, size=(batch,)).astype(np.int32))
    dt = _train_bench(LeNet5(10), ClassNLLCriterion(), x, y,
                      SGD(learningrate=0.01), steps, warmup,
                      bf16_inputs=True)
    v = batch * steps / dt
    return {"metric": "lenet_mnist_train_images_per_sec", "value": round(v, 1),
            "unit": "images/sec", "vs_baseline": round(v / _BASE["lenet_mnist"], 3)}


def bench_vgg(on_tpu):
    import numpy as np
    import jax.numpy as jnp
    from bigdl_tpu.models import VggForCifar10
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD

    batch = _sized(on_tpu, 256, 4)
    steps, warmup = _sized(on_tpu, 15, 2), _sized(on_tpu, 3, 1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 3, 32, 32).astype(np.float32))
    y = jnp.asarray(rng.randint(1, 11, size=(batch,)).astype(np.int32))
    dt = _train_bench(VggForCifar10(10), ClassNLLCriterion(), x, y,
                      SGD(learningrate=0.01), steps, warmup,
                      bf16_inputs=True)
    v = batch * steps / dt
    return {"metric": "vgg16_cifar10_train_images_per_sec", "value": round(v, 1),
            "unit": "images/sec", "vs_baseline": round(v / _BASE["vgg16_cifar10"], 3)}


def bench_lstm_ptb(on_tpu):
    import numpy as np
    import jax.numpy as jnp
    from bigdl_tpu.models import PTBModel
    from bigdl_tpu.nn import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.optim import SGD

    vocab, seqlen = 10000, _sized(on_tpu, 35, 12)
    batch = _sized(on_tpu, 64, 4)
    steps, warmup = _sized(on_tpu, 15, 2), _sized(on_tpu, 3, 1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(1, vocab + 1,
                                size=(batch, seqlen)).astype(np.float32))
    y = jnp.asarray(rng.randint(1, vocab + 1,
                                size=(batch, seqlen)).astype(np.float32))
    # PTBModel already ends in LogSoftMax → NLL criterion (not CE, which
    # would apply log_softmax twice)
    model = PTBModel(vocab, hidden_size=_sized(on_tpu, 650, 64), num_layers=2)
    crit = TimeDistributedCriterion(ClassNLLCriterion())
    dt = _train_bench(model, crit, x, y, SGD(learningrate=0.01), steps, warmup)
    v = batch * seqlen * steps / dt
    return {"metric": "lstm_ptb_train_tokens_per_sec", "value": round(v, 1),
            "unit": "tokens/sec", "vs_baseline": round(v / _BASE["lstm_ptb"], 3)}


def bench_inception_int8(on_tpu):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models import Inception_v1_NoAuxClassifier
    from bigdl_tpu.quantization import quantize

    batch = _sized(on_tpu, 128, 2)
    size = _sized(on_tpu, 224, 64)
    steps, warmup = _sized(on_tpu, 20, 2), _sized(on_tpu, 3, 1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 3, size, size).astype(np.float32))

    model = Inception_v1_NoAuxClassifier(1000)
    model.ensure_initialized()
    model.evaluate()
    # calibrated static activation scales: the dynamic path recomputes a
    # full abs-max reduction per quantized layer per batch, which eats the
    # int8 MXU gain; calibration bakes the scales into params
    from bigdl_tpu.quantization import calibrate
    scales = calibrate(model, [np.asarray(
        rng.randn(_sized(on_tpu, 8, 2), 3, size, size).astype(np.float32))])
    qmodel = quantize(model, calibration=scales)
    params, mstate = qmodel.params, qmodel.state

    def fwd(params, x):
        out, _ = qmodel.apply(params, mstate, x, training=False)
        return out

    step = jax.jit(fwd)
    for _ in range(warmup):
        out = step(params, x)
    np.asarray(out[0, 0])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(params, x)
    np.asarray(out[0, 0])
    dt = time.perf_counter() - t0
    v = batch * steps / dt
    return {"metric": "inception_v1_int8_infer_images_per_sec",
            "value": round(v, 1), "unit": "images/sec",
            "vs_baseline": round(v / _BASE["inception_v1_int8"], 3)}


def _timed_lm_steps(step, carry, args, steps, warmup):
    """Shared LM-bench harness: warmup, one full sync, timed chained
    steps, final sync + NaN guard. ``step(*carry, *args) -> (loss,
    *carry)`` must be an AOT-compiled executable with donated carry."""
    for _ in range(warmup):
        loss, *carry = step(*carry, *args)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, *carry = step(*carry, *args)
    final = float(loss)
    dt = time.perf_counter() - t0
    assert final == final, "NaN loss in LM bench"
    return dt


def _run_remat_arms(run_arm):
    """Shared remat policy for the LM benches. ``run_arm(remat) -> dt``
    builds, compiles and times one arm (its frame owns every buffer, so
    an OOM unwinds cleanly). BENCH_LM_REMAT: auto (default) tries the
    remat-free arm and falls back to remat=True on RESOURCE_EXHAUSTED;
    0/1 pin an arm for A/Bs. Returns (dt, remat_used)."""
    env = os.environ.get("BENCH_LM_REMAT", "auto")
    if env not in ("0", "1", "auto"):
        # an unknown value must not silently benchmark the wrong arm
        raise SystemExit(f"BENCH_LM_REMAT={env!r}: expected auto | 1 | 0")
    arms = {"0": [False], "1": [True], "auto": [False, True]}[env]
    last_oom = None
    for remat in arms:
        try:
            return run_arm(remat), remat
        except Exception as e:  # HBM OOM surfaces as XlaRuntimeError
            if remat is not arms[-1] and "RESOURCE_EXHAUSTED" in str(e):
                last_oom = str(e)[:200]
                continue
            if last_oom:
                raise RuntimeError(
                    f"remat={remat} failed after the remat=False arm "
                    f"already hit RESOURCE_EXHAUSTED ({last_oom})") from e
            raise


def _lm_model_flops(B, T, H, F, L, V, causal=True):
    """Analytic model FLOPs for one LM training step (fwd + 2x bwd).

    XLA's compiled cost analysis cannot see inside ``pallas_call`` custom
    calls, so with the flash kernel in the model the attention matmuls would
    vanish from a cost-analysis-based numerator and MFU would be understated.
    Standard model-FLOPs accounting instead: per layer 4 qkvo projections,
    the two T^2 attention matmuls (halved when causal — the kernel really
    skips blocks above the diagonal), two FFN matmuls; plus the tied vocab
    projection. Flash/remat RECOMPUTE flops are deliberately excluded — MFU
    counts useful model flops only (the conservative convention)."""
    per_layer = (4 * 2 * B * T * H * H
                 + (2 * 2 * B * T * T * H) * (0.5 if causal else 1.0)
                 + 2 * 2 * B * T * H * F)
    fwd = L * per_layer + 2 * B * T * H * V
    return 3.0 * fwd


def bench_transformer_lm(on_tpu):
    """GPT-style TransformerLM train step, bf16 compute + f32 master params.

    Not a BASELINE.json config (the reference has no transformer benchmark)
    but the honest MFU showcase: matmul-dominated, so the MXU packs far
    better than ResNet's stage-1 convs.

    Round-3 memory story (the r2 cache kept a B16/T1024 OOM line as the bug
    report): flash attention in the model path (no (B,H,T,T) scores), remat
    over blocks, and a chunked fused projection+CE loss head
    (models.transformer_lm.lm_loss_chunked) — B16/T1024/12L now fits a
    16 GB v5e. MFU from analytic model FLOPs (see _lm_model_flops)."""
    from bigdl_tpu.utils.amp import bf16_params
    import numpy as np
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models import TransformerLM, lm_loss_chunked
    from bigdl_tpu.optim import SGD

    batch = _sized(on_tpu, int(os.environ.get("BENCH_LM_BATCH", 16)), 2)
    seqlen = _sized(on_tpu, 1024, 32)
    H, F, V = (1024, 4096, 32000)
    L = _sized(on_tpu, 12, 2)
    steps, warmup = _sized(on_tpu, 15, 2), _sized(on_tpu, 3, 1)
    optim = SGD(learningrate=0.01, momentum=0.9)

    rng = np.random.RandomState(0)
    ids = rng.randint(1, V, size=(batch, seqlen + 1)).astype(np.int32)
    x = jnp.asarray(ids[:, :-1])
    y = jnp.asarray(ids[:, 1:])

    def run_arm(remat):
        model = TransformerLM(vocab_size=V, hidden_size=H, num_heads=16,
                              filter_size=F, num_layers=L, max_len=seqlen,
                              remat=remat)
        params, _ = model.init(jax.random.PRNGKey(0))
        opt_state = optim.init_state(params)

        def train_step(params, opt_state, x, y, lr):
            def loss_fn(p):
                p16 = bf16_params(p)
                h = model.hidden_states(p16, x, training=True,
                                        rng=jax.random.PRNGKey(0))
                return lm_loss_chunked(h, p16["embed"], y, chunk=128)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt = optim.update(grads, params, opt_state,
                                               lr)
            return loss, new_params, new_opt

        lr = jnp.float32(0.01)
        step = jax.jit(train_step, donate_argnums=(0, 1)) \
                  .lower(params, opt_state, x, y, lr).compile()
        return _timed_lm_steps(step, [params, opt_state], (x, y, lr),
                               steps, warmup)

    dt, remat = _run_remat_arms(run_arm)
    v = batch * seqlen * steps / dt
    # vs_baseline is null: the reference has no transformer config, and a
    # ratio against the LSTM anchor would be a meaningless cross-model number
    r = {"metric": "transformer_lm_train_tokens_per_sec", "value": round(v, 1),
         "unit": "tokens/sec", "vs_baseline": None, "remat": bool(remat)}
    if on_tpu:
        from bench import _peak_flops
        peak = _peak_flops(jax.devices()[0].device_kind)
        flops_per_step = _lm_model_flops(batch, seqlen, H, F, L, V)
        r["mfu"] = round(flops_per_step * steps / dt / peak, 4)
    return r


def bench_moe_lm(on_tpu):
    """Switch-MoE Transformer LM train step (bf16 compute, f32 masters):
    the sparse-FFN showcase. MFU counts ACTIVATED expert FLOPs only
    (top-1 routing runs one expert per token — the sparse win is
    parameters, not per-token compute), plus router/aux overhead omitted
    (conservative numerator, same convention as _lm_model_flops)."""
    from bigdl_tpu.utils.amp import bf16_params
    import numpy as np
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models import MoETransformerLM
    from bigdl_tpu.optim import SGD

    batch = _sized(on_tpu, 8, 2)
    seqlen = _sized(on_tpu, 1024, 32)
    H, F, V = (1024, 4096, 32000)
    L = _sized(on_tpu, 12, 2)
    E = 8
    steps, warmup = _sized(on_tpu, 10, 2), _sized(on_tpu, 3, 1)
    optim = SGD(learningrate=0.01, momentum=0.9)

    rng = np.random.RandomState(0)
    ids = rng.randint(1, V, size=(batch, seqlen + 1)).astype(np.int32)
    x = jnp.asarray(ids[:, :-1])
    y = jnp.asarray(ids[:, 1:])

    def run_arm(remat):
        model = MoETransformerLM(vocab_size=V, hidden_size=H, num_heads=16,
                                 filter_size=F, num_layers=L, n_experts=E,
                                 moe_every=2, max_len=seqlen, remat=remat)
        params, _ = model.init(jax.random.PRNGKey(0))
        opt_state = optim.init_state(params)

        def train_step(params, opt_state, x, y, lr):
            def loss_fn(p):
                p16 = bf16_params(p)
                from bigdl_tpu.models import lm_loss_chunked
                h, aux = model.hidden_states(p16, x, training=True,
                                             rng=jax.random.PRNGKey(0))
                return (lm_loss_chunked(h, p16["embed"], y, chunk=128)
                        + 0.01 * aux.astype(jnp.float32))
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt = optim.update(grads, params, opt_state,
                                               lr)
            return loss, new_params, new_opt

        lr = jnp.float32(0.01)
        step = jax.jit(train_step, donate_argnums=(0, 1)) \
                  .lower(params, opt_state, x, y, lr).compile()
        return _timed_lm_steps(step, [params, opt_state], (x, y, lr),
                               steps, warmup)

    dt, remat = _run_remat_arms(run_arm)
    v = batch * seqlen * steps / dt
    r = {"metric": "moe_lm_train_tokens_per_sec", "value": round(v, 1),
         "unit": "tokens/sec", "vs_baseline": None, "n_experts": E,
         "remat": bool(remat)}
    if on_tpu:
        from bench import _peak_flops
        peak = _peak_flops(jax.devices()[0].device_kind)
        flops = _lm_model_flops(batch, seqlen, H, F, L, V)  # top-1: dense-
        # equivalent activated FLOPs per token (one expert == one FFN)
        r["mfu"] = round(flops * steps / dt / peak, 4)
    return r


def bench_lm_decode(on_tpu):
    """Autoregressive decode throughput: KV-cache generation on the
    flagship LM (B8, prompt 128, 256 new tokens), bf16 weights, with the
    weight-only-int8 decode ratio alongside — decode is weight-bandwidth
    bound, so int8 halves the HBM traffic per token. Prefill cost is
    measured separately (1-token generate) and subtracted."""
    from bigdl_tpu.utils.amp import bf16_params
    import numpy as np
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.quantization import quantize_lm_params

    B = _sized(on_tpu, 8, 2)
    prompt_len = _sized(on_tpu, 128, 8)
    new_tokens = _sized(on_tpu, 256, 6)
    H, F, V = ((1024, 4096, 32000) if on_tpu else (64, 256, 128))
    L = _sized(on_tpu, 12, 2)
    heads = 16 if on_tpu else 2
    # BENCH_DECODE_KV_HEADS < heads = grouped-query attention arm: the
    # KV caches shrink by the group factor (decode streams the cache
    # every step, so this is a direct HBM-bandwidth lever)
    kvh = int(os.environ.get("BENCH_DECODE_KV_HEADS", heads))
    model = TransformerLM(vocab_size=V, hidden_size=H, num_heads=heads,
                          filter_size=F, num_layers=L,
                          max_len=prompt_len + new_tokens,
                          num_kv_heads=kvh if kvh != heads else None)
    params, _ = model.init(jax.random.PRNGKey(0))
    params = bf16_params(params)
    prompt = jnp.asarray(np.random.RandomState(0).randint(
        1, V, (B, prompt_len)), jnp.int32)

    def timed_decode(p):
        gen = jax.jit(lambda pp, x: model.generate(
            pp, x, max_new_tokens=new_tokens))
        gen1 = jax.jit(lambda pp, x: model.generate(pp, x,
                                                    max_new_tokens=1))
        out = gen(p, prompt)
        np.asarray(out[0, -1])            # compile + run once
        o1 = gen1(p, prompt)
        np.asarray(o1[0, -1])
        t0 = time.perf_counter()
        o1 = gen1(p, prompt)
        np.asarray(o1[0, -1])
        dt1 = time.perf_counter() - t0    # ~prefill + 1 token
        t0 = time.perf_counter()
        out = gen(p, prompt)
        np.asarray(out[0, -1])
        dt = time.perf_counter() - t0
        denom = dt - dt1
        if denom < 0.1 * dt:  # subtraction at the timer noise floor
            # (smoke scales): report the unsubtracted rate instead of
            # an arbitrarily inflated fiction
            denom = dt
        return B * (new_tokens - 1) / denom

    # BENCH_DECODE_WBITS selects the weight-only arms (comma list, e.g.
    # "8,4"): int8 is per-out-channel, int4 is group-wise packed s4 on
    # TPU (half the int8 param stream, quarter of bf16). One run times
    # ONE bf16 baseline and every requested quantized arm against it.
    wbits_list = [int(b) for b in
                  os.environ.get("BENCH_DECODE_WBITS", "8").split(",")]
    if any(b not in (4, 8) for b in wbits_list):
        # fail BEFORE the bf16 baseline burns chip time
        raise ValueError(f"BENCH_DECODE_WBITS must be 4s/8s, "
                         f"got {wbits_list}")
    bf16_tps = timed_decode(params)
    quant = {}
    for wb in wbits_list:
        tps = timed_decode(quantize_lm_params(params, bits=wb))
        quant[f"int{wb}_tokens_per_sec"] = round(tps, 1)
        quant[f"int{wb}_speedup"] = round(tps / max(bf16_tps, 1e-9), 3)

    # BENCH_DECODE_SPEC=k: the speculative-decoding verify primitive —
    # one (k+1)-token decode_chunk vs k+1 sequential decode_one steps.
    # Weight-independent (acceptance rates need trained models); the
    # ratio IS the mechanical case for nn/speculative.py: if a chunked
    # verify costs about one step, a draft with acceptance a yields
    # ~(1+a*k)/(1+k*draft_cost_ratio) tokens per weight stream.
    spec_k = int(os.environ.get("BENCH_DECODE_SPEC", 0))
    if spec_k > 0:
        pos = prompt_len
        _, caches = jax.jit(
            lambda p, x: model.prefill(p, x, prompt_len + spec_k + 2))(
                params, prompt)
        toks = jnp.asarray(np.random.RandomState(2).randint(
            1, V, (B, spec_k + 1)), jnp.int32)

        chunk_fn = jax.jit(lambda p, t, c: model.decode_chunk(
            p, t, pos, c)[0])

        def seq_all(p, t, c):
            outs = []
            for i in range(spec_k + 1):
                lg, c = model.decode_one(p, t[:, i], pos + i, c)
                outs.append(lg)
            return jnp.stack(outs, 1)
        seq_fn = jax.jit(seq_all)

        def best_of(fn, n=5):
            fn(params, toks, caches).block_until_ready()   # compile
            ts = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn(params, toks, caches).block_until_ready()
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t_chunk, t_seq = best_of(chunk_fn), best_of(seq_fn)
        quant["spec_chunk_k"] = spec_k
        quant["spec_verify_speedup"] = round(t_seq / max(t_chunk, 1e-9), 3)
        quant["spec_chunk_ms"] = round(t_chunk * 1e3, 3)

    # decode is HBM-bandwidth bound: every step streams all params plus
    # the live KV cache. Bytes per BATCH step (B tokens): params once +
    # avg cache (k+v, kvh heads, mean seq length over the decode range).
    import jax
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    d_head = H // heads
    t_avg = prompt_len + new_tokens / 2
    cache_bytes = 2 * L * kvh * d_head * t_avg * B * 2     # bf16
    step_bytes = n_params * 2 + cache_bytes
    bytes_per_token = step_bytes / B
    # bandwidth utilization vs the chip's public HBM peak (v5e: 819 GB/s)
    bw_util = (bf16_tps * bytes_per_token) / 819e9 if on_tpu else None
    return {"metric": "lm_decode_tokens_per_sec", "value": round(bf16_tps, 1),
            "unit": "tokens/sec", "vs_baseline": None,
            "kv_heads": kvh,
            "bytes_per_token": round(bytes_per_token / 1e6, 2),
            "hbm_bw_util": round(bw_util, 3) if bw_util else None,
            **quant}


def bench_realdata(on_tpu):
    """ResNet-50 fed from real JPEG files via the C++ prefetcher — the
    implementation lives next to the synthetic headline in bench.py."""
    from bench import bench_resnet50_realdata
    return bench_resnet50_realdata()


# config key -> bench fn name (the keys `python bench.py --config` takes)
CONFIGS = {
    "lenet": "bench_lenet",
    "vgg": "bench_vgg",
    "lstm": "bench_lstm_ptb",
    "inception_int8": "bench_inception_int8",
    "transformer": "bench_transformer_lm",
    "moe": "bench_moe_lm",
    "decode": "bench_lm_decode",
    "realdata": "bench_realdata",
}


def bench_one(key: str):
    """Run ONE named config. Exceptions propagate: bench.py's main prints
    a ``*_failed`` line for the config and exits non-zero at the end."""
    import jax
    from bigdl_tpu import observability as obs
    backend = jax.default_backend()
    with obs.span(f"bench/{key}"):
        r = globals()[CONFIGS[key]](backend == "tpu")
    r["backend"] = backend
    return r
