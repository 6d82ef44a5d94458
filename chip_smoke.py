#!/usr/bin/env python3
"""Does the system still start on the chip? One process, one run, pass or fail.

``python chip_smoke.py`` drives the two main paths once, through the entry
points a user calls, at the full width and depth of the dense LM the repo
benchmarks (``TransformerLM`` vocab 32000, hidden 1024, 16 heads, filter
4096, 12 layers), with random weights made from a seed:

* **kernels** - each Pallas kernel on the default path, called directly at
  the smoke's own shapes and compared on the chip with its ``jax.numpy``
  reference: flash attention forward and gradients against
  ``nn.attention.dot_product_attention``, paged decode attention against
  ``Attention._paged_gather_attend`` at ``S=1`` and at one prefill-chunk
  shape;
* **trainer** - ``optim.Optimizer`` over a synthetic token dataset exactly
  one batch long (every step sees the same tokens): a compile step plus
  five more, loss finite on every step and lower at the end than at the
  start, state resident on the accelerator, no fault-policy retry;
* **server** - ``serving.Router`` over one ``DecodeScheduler`` started with
  warm-up, then greedy requests from a few client threads with prompts
  spread over 32-1024 tokens and 16-128 new tokens. Every future resolves,
  every token agrees with the dense reference (rule below), KV blocks in
  use return to 0, nothing compiles after warm-up, and the counters say the
  paged kernel served (``kernels/paged_attn_programs >= 1``,
  ``kernels/paged_attn_dense_programs == 0``, ``compile/degraded == 0``, no
  step replay).

With more than one device it also runs the same two paths spread over all
of them: ``Optimizer`` then resolves to ``DistriOptimizer`` over
``data_parallel_mesh()`` (``replicated``), a second trainer runs in
``zero1`` mode, and a second server runs ``DecodeScheduler(mesh=,
placement="tp")`` over a ``model`` axis. Each shows its work is on every
device: the batch's and the state's shards span all of them and every
device reports ``bytes_in_use > 0``.

Token rule. The server prefills in chunks through the paged kernel while
``model.generate`` prefills in one flash pass, and the MXU multiplies f32
operands in bf16 passes, so the two can differ in the last bits of a logit
and a near-tie between two tokens can flip. Each generated token is
therefore checked against a teacher-forced dense forward of the same model
on the same device: the reference logit of the served token must be within
``Env.logit_tol`` (2e-2 on the chip) of the reference maximum at that
position. ``model.generate`` is also run for a few requests; where it is
not token-for-token equal the first divergence is reported, and by the
rule above it is such a near-tie.

It exits non-zero, and prints no result line, when the platform is not
``tpu`` or when it cannot import the repo from its own directory. Any phase
that fails ends the run there: nothing is caught and skipped.

``--rehearse`` is the explicit CPU rehearsal: tiny sizes, Pallas kernels in
interpret mode, every report line marked REHEARSAL. It checks the script's own
plumbing before chip time is spent; it is never what the script does by
itself when it finds no chip. ``--phases a,b`` limits the run to the named
phases (chip time is budgeted; a re-run of one phase should not pay for
the rest).

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926

# The dense LM bench_extra.py sizes for a TPU, neither width nor depth cut.
# Batch, sequence length and slot count are sized to one chip's 16 GB: the
# trainer holds f32 params + grads + Adam moments (~2.9 GB) and ~1 MB of
# activations per token; the server holds f32 params (0.7 GB) and a page
# pool of slots * max_seq_len positions (0.9 GB), twice while a step runs.
FULL = dict(
    vocab=32000, hidden=1024, heads=16, filt=4096, layers=12,
    train_seq=1024, train_batch_per_device=4, train_steps=6,
    slots=8, block_size=16, max_seq_len=1152, prefill_chunk=128,
    requests=16, clients=4, prompt_range=(32, 1024), new_range=(16, 128),
    generate_checks=2)
TINY = dict(
    vocab=128, hidden=64, heads=4, filt=128, layers=2,
    train_seq=64, train_batch_per_device=2, train_steps=6,
    slots=4, block_size=16, max_seq_len=128, prefill_chunk=16,
    requests=6, clients=2, prompt_range=(8, 64), new_range=(4, 16),
    generate_checks=2)


def check(cond, what):
    """A failed check ends the run: no phase is skipped or retried."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED - {what}")


class CompileWatch:
    """Counts what JAX compiles (or loads from the persistent cache): one
    ``backend_compile_duration`` event per program, named."""

    def __init__(self):
        import jax.monitoring
        self.programs = []          # (name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append((kw.get("fun_name", "?"), duration))

    def mark(self):
        return len(self.programs)

    def since(self, mark):
        new = self.programs[mark:]
        return len(new), sum(d for _, d in new), [n for n, _ in new]


def cache_counts():
    from bigdl_tpu.utils import engine
    stats = engine.compilation_cache_stats()
    return stats["hits"], stats["misses"]


def counter(name):
    from bigdl_tpu import observability as obs
    return int(obs.registry().counter(name).value)


# ------------------------------------------------------------------ kernels

def _max_err(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return (float(jnp.max(jnp.abs(a - b))),
            max(1.0, float(jnp.max(jnp.abs(b)))))


def phase_kernels(cfg, env):
    """Each kernel on the default path, directly, against its jax.numpy
    reference computed at the highest matmul precision. Tolerance: the
    largest absolute error over the largest reference magnitude (at least
    1) stays under ``env.kernel_tol`` - 2e-2 on the chip (the MXU's bf16
    passes over f32 operands), 1e-4 in the CPU interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu.kernels.flash_attention import (flash_attention_fused,
                                                   flash_attention_qkv,
                                                   flash_attention_rows,
                                                   heads_per_block)
    from bigdl_tpu.kernels.paged_attention import paged_decode_attention
    from bigdl_tpu.nn.attention import (Attention, causal_mask,
                                        dot_product_attention)
    rng = np.random.RandomState(SEED)
    H, D = cfg["heads"], cfg["hidden"] // cfg["heads"]
    B, T = cfg["train_batch_per_device"], cfg["train_seq"]
    out = {}

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def within(name, got, want):
        err, scale = _max_err(got, want)
        out[name] = {"max_abs_err": err, "ref_scale": scale}
        check(bool(jnp.isfinite(got.astype(jnp.float32)).all()),
              f"{name}: kernel output is not finite")
        check(err <= env.kernel_tol * scale,
              f"{name}: max|kernel - reference| = {err:.3e} exceeds "
              f"{env.kernel_tol:g} x {scale:.3g}")

    # flash attention, forward and gradients, at the trainer's shape
    q, k, v, w = (rand(B, H, T, D) for _ in range(4))
    mask = causal_mask(T)
    flash = lambda q, k, v: flash_attention_fused(  # noqa: E731
        q, k, v, causal=True, interpret=env.interpret)
    dense = lambda q, k, v: dot_product_attention(  # noqa: E731
        q, k, v, mask)
    within(f"flash_fwd B{B} H{H} T{T} D{D} f32",
           jax.jit(flash)(q, k, v), reference(dense, q, k, v))
    g_kernel = jax.jit(jax.grad(lambda q, k, v: (flash(q, k, v) * w).sum(),
                                argnums=(0, 1, 2)))(q, k, v)
    g_dense = reference(jax.grad(lambda q, k, v: (dense(q, k, v) * w).sum(),
                                 argnums=(0, 1, 2)), q, k, v)
    for name, a, b in zip("qkv", g_kernel, g_dense):
        within(f"flash_bwd d{name}", a, b)
    if heads_per_block(H, D):
        # the trainer's own entry: the same attention indexed on the
        # projections' layout [B, T, H*D], against the same references
        rows = lambda x: x.transpose(0, 2, 1, 3).reshape(B, T, H * D)  # noqa: E731
        flash_r = lambda q, k, v: flash_attention_rows(  # noqa: E731
            q, k, v, H, causal=True, interpret=env.interpret)
        qr, kr, vr, wr = rows(q), rows(k), rows(v), rows(w)
        within(f"flash_rows_fwd B{B} T{T} H{H}xD{D} f32",
               jax.jit(flash_r)(qr, kr, vr), rows(reference(dense, q, k, v)))
        g_rows = jax.jit(jax.grad(
            lambda q, k, v: (flash_r(q, k, v) * wr).sum(),
            argnums=(0, 1, 2)))(qr, kr, vr)
        for name, a, b in zip("qkv", g_rows, g_dense):
            within(f"flash_rows_bwd d{name}", a, rows(b))
        # self-attention's entry: the same kernels read q, k and v out of
        # ONE array [B, T, 3*H*D], so nothing differs, to the last bit
        fused = lambda x: flash_attention_qkv(  # noqa: E731
            x, H, causal=True, interpret=env.interpret)
        qkv = jnp.concatenate([qr, kr, vr], axis=-1)
        g_qkv = jax.jit(jax.grad(lambda x: (fused(x) * wr).sum()))(qkv)
        for name, a, b in (
                ("flash_qkv_fwd", jax.jit(fused)(qkv),
                 jax.jit(flash_r)(qr, kr, vr)),
                ("flash_qkv_bwd", g_qkv, jnp.concatenate(g_rows, axis=-1))):
            out[f"{name} == flash_rows"] = bool((a == b).all())
            check(out[f"{name} == flash_rows"],
                  f"{name}: differs from the rows entry on the slices")

    # paged decode attention at the server's geometry: S=1 over a full
    # slot bucket, and one prefill chunk; tables are a random permutation
    # of the pool so the kernel's page lookups are exercised
    bs, slots = cfg["block_size"], cfg["slots"]
    nblk = cfg["max_seq_len"] // bs
    pool = slots * nblk + 1
    kp, vp = rand(pool, H, bs, D), rand(pool, H, bs, D)
    attn = Attention(cfg["hidden"], cfg["heads"])
    for Bq, S in ((slots, 1), (1, cfg["prefill_chunk"])):
        qd = rand(Bq, H, S, D)
        tables = jnp.asarray(1 + rng.permutation(pool - 1)[:Bq * nblk]
                             .reshape(Bq, nblk), jnp.int32)
        pos = jnp.asarray(np.linspace(0, cfg["max_seq_len"] - S, Bq)
                          .astype(np.int32)[::-1].copy())
        pos_s = pos[:, None] + jnp.arange(S)[None, :]
        got = jax.jit(lambda q, kp, vp, t, p: paged_decode_attention(
            q, kp, vp, t, p, interpret=env.interpret))(qd, kp, vp, tables,
                                                       pos)
        want = reference(attn._paged_gather_attend, qd, kp, vp, tables,
                         pos_s)
        within(f"paged B{Bq} S{S} bs{bs} kvH{H} D{D} f32", got, want)
    return out


# ------------------------------------------------------------------ trainer

def _live_array_report(batch_shape, n_devices, big):
    """Where the process's live arrays sit, read from JAX itself at the
    last training step: the batch's shards, how many big arrays (``big``
    elements or more: a weight matrix) are sharded and how many
    replicated, and the bytes held per device."""
    import jax
    per_device, batch, sharded, replicated, platforms = {}, [], 0, 0, set()
    for a in jax.live_arrays():
        shards = a.addressable_shards
        for s in shards:
            per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                       + s.data.nbytes)
            platforms.add(s.device.platform)
        spans = len({s.device.id for s in shards})
        if tuple(a.shape) == tuple(batch_shape):
            batch.append({"devices": spans,
                          "shard_shape": list(shards[0].data.shape)})
        elif a.size >= big and spans == n_devices:
            if a.sharding.is_fully_replicated:
                replicated += 1
            else:
                sharded += 1
    return {"platforms": sorted(platforms), "batch_shards": batch,
            "big_arrays_sharded": sharded,
            "big_arrays_replicated": replicated,
            "live_mb_per_device": {str(d): round(b / 2**20, 1)
                                   for d, b in sorted(per_device.items())}}


def _device_memory(env):
    """``bytes_in_use`` as every device reports it (TPU only: the CPU
    backend has no memory_stats, and a rehearsal says so)."""
    import jax
    out = {}
    for d in jax.devices():
        stats = d.memory_stats()
        if stats is None:
            check(env.rehearsal, f"device {d.id} reports no memory_stats")
            out[str(d.id)] = "not reported on this platform"
            continue
        out[str(d.id)] = int(stats["bytes_in_use"])
        check(stats["bytes_in_use"] > 0,
              f"device {d.id} reports bytes_in_use == 0: the work is not "
              "on every chip")
    return out


def phase_trainer(cfg, env, parameter_mode=None):
    """``Optimizer`` -> LocalOptimizer on one device, DistriOptimizer over
    ``data_parallel_mesh()`` on several (``parameter_mode`` as given)."""
    import jax
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim import (Adam, DistriOptimizer, LocalOptimizer,
                                 Optimizer, TrainSummary, Trigger)
    from bigdl_tpu.utils import engine

    n = len(jax.devices())
    B, T = cfg["train_batch_per_device"] * n, cfg["train_seq"]
    steps = cfg["train_steps"]
    engine.set_seed(SEED)
    ids = np.random.RandomState(SEED).randint(
        1, cfg["vocab"], size=(B, T + 1))
    # exactly one batch: every step sees the same tokens (ids ride the
    # reference's float Sample convention; the model casts them back)
    dataset = DataSet.array([
        Sample(ids[i, :-1].astype(np.float32), ids[i, 1:].astype(np.float32))
        for i in range(B)])
    model = TransformerLM(vocab_size=cfg["vocab"], hidden_size=cfg["hidden"],
                          num_heads=cfg["heads"], filter_size=cfg["filt"],
                          num_layers=cfg["layers"], max_len=T)

    probe = {}

    def end(state):
        done = state["neval"] >= steps
        if done:    # last step: the state and the batch are still live
            probe.update(_live_array_report((B, T), n, cfg["hidden"] ** 2))
            probe["bytes_in_use"] = _device_memory(env)
        return done

    kw = {"parameter_mode": parameter_mode} if parameter_mode else {}
    opt = Optimizer(model=model, training_set=dataset,
                    criterion=nn.LMCriterion(padding_value=0),
                    optim_method=Adam(learningrate=1e-3),
                    end_trigger=Trigger(end), batch_size=B, **kw)
    check(isinstance(opt, DistriOptimizer if n > 1 else LocalOptimizer),
          f"Optimizer resolved to {type(opt).__name__} on {n} device(s)")
    logdir = tempfile.mkdtemp(prefix="chip_smoke_summary_")
    try:
        summary = TrainSummary(logdir, "chip_smoke")
        opt.set_train_summary(summary)
        t0 = time.perf_counter()
        opt.optimize()
        wall = time.perf_counter() - t0
        summary.close()
        losses = [float(v) for _, v in summary.read_scalar("Loss")]
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(not opt.metrics.values.get("fault_retries"),
          "the optimizer's fault policy replayed a step")
    check(not opt.metrics.values.get("nan_skips"), "a NaN step was skipped")
    check(probe["platforms"] == [env.platform],
          f"live arrays sit on {probe['platforms']}, not {env.platform}")
    check(len(probe["batch_shards"]) >= 2 and all(
        b["devices"] == n and b["shard_shape"] == [B // n, T]
        for b in probe["batch_shards"]),
        f"the batch's shards do not span {n} device(s): "
        f"{probe['batch_shards']}")
    if parameter_mode == "zero1":
        check(probe["big_arrays_sharded"] >= 2,
              "zero1: no optimizer state is sharded over the data axis")
    elif n > 1:
        check(probe["big_arrays_replicated"] >= 2 * cfg["layers"],
              "replicated: the params do not sit on every device")
    step_times = opt.metrics.values.get("step_time", [])
    return {"optimizer": type(opt).__name__,
            "parameter_mode": parameter_mode or
            ("replicated" if n > 1 else "local"),
            "devices": n, "batch": B, "seq": T, "steps": steps,
            "losses": [round(v, 4) for v in losses],
            "first_step_s": round(step_times[0], 2) if step_times else None,
            "later_step_s": [round(t, 3) for t in step_times[1:]],
            "optimize_wall_s": round(wall, 2), **probe}


# ------------------------------------------------------------------- server

def _request_plan(cfg):
    """(prompt, max_new_tokens) per request: prompt lengths spread
    log-uniformly over ``prompt_range`` with both ends included."""
    import numpy as np
    rng = np.random.RandomState(SEED + 1)
    n = cfg["requests"]
    lo, hi = cfg["prompt_range"]
    lens = np.exp(rng.uniform(np.log(lo), np.log(hi), size=n)).astype(int)
    lens[0], lens[-1] = lo, hi
    news = rng.randint(cfg["new_range"][0], cfg["new_range"][1] + 1, size=n)
    return [(rng.randint(1, cfg["vocab"], size=int(t)).astype(np.int32),
             int(m)) for t, m in zip(lens, news)]


def phase_server(cfg, env, watch, tp=False):
    """``Router`` over one ``DecodeScheduler``; ``tp`` places it over a
    ``model`` axis of every device (kv heads split, so the pages shard
    and the paged kernel runs under shard_map)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.serving import DecodeScheduler, Router
    from bigdl_tpu.utils import engine

    n = len(jax.devices())
    L = cfg["max_seq_len"]
    engine.set_seed(SEED)
    model = TransformerLM(vocab_size=cfg["vocab"], hidden_size=cfg["hidden"],
                          num_heads=cfg["heads"], filter_size=cfg["filt"],
                          num_layers=cfg["layers"], max_len=L)
    model.ensure_initialized()
    placement = {}
    if tp:
        check(cfg["heads"] % n == 0, f"{cfg['heads']} kv heads do not split "
                                     f"{n} ways")
        placement = dict(mesh=jax.sharding.Mesh(np.array(jax.devices()),
                                                ("model",)),
                         placement="tp")
    before = {c: counter(c) for c in (
        "kernels/paged_attn_programs", "kernels/paged_attn_dense_programs",
        "compile/degraded", "serve/step_replays")}
    sched = DecodeScheduler(
        model, max_slots=cfg["slots"], block_size=cfg["block_size"],
        max_seq_len=L, prefill_chunk=cfg["prefill_chunk"],
        name="tp" if tp else "chip0", **placement)
    router = Router([sched])
    plan = _request_plan(cfg)
    outputs, errors = [None] * len(plan), []

    def client(idxs):
        try:
            for i in idxs:
                prompt, new = plan[i]
                outputs[i] = np.asarray(router.submit(
                    prompt, max_new_tokens=new).result(timeout=900))
        except BaseException as e:  # noqa: BLE001 - re-raised by main
            errors.append(e)

    t0 = time.perf_counter()
    mark = watch.mark()
    router.start()                   # DecodeScheduler.start(warmup=True)
    try:
        warm_n, warm_s, _ = watch.since(mark)
        t_warm = time.perf_counter() - t0
        pages = sched.kv.pages()[0][0]
        page_devices = len({s.device.id for s in pages.addressable_shards})
        page_shard = list(pages.addressable_shards[0].data.shape)
        mark = watch.mark()
        t1 = time.perf_counter()
        threads = [threading.Thread(
            target=client, args=(range(c, len(plan), cfg["clients"]),))
            for c in range(cfg["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_serve = time.perf_counter() - t1
        if errors:
            raise errors[0]
        late_n, _, late_names = watch.since(mark)
        mid = sched.stats()
        mem = _device_memory(env) if tp else None
    finally:
        router.shutdown()
    st = sched.stats()

    check(all(o is not None and len(o) == new
              for o, (_, new) in zip(outputs, plan)),
          "a request resolved with the wrong number of tokens")
    check(st["completed"] == len(plan),
          f"{st['completed']} of {len(plan)} requests completed")
    check(late_n == 0, f"{late_n} program(s) compiled after warm-up: "
                       f"{late_names}")
    check(mid["kv"]["blocks_in_use"] == (mid["prefix"] or {}).get(
        "entries", 0), "KV blocks outlived the requests that owned them")
    check(st["kv"]["blocks_in_use"] == 0,
          f"{st['kv']['blocks_in_use']} KV blocks still in use at shutdown")
    delta = {c: counter(c) - v for c, v in before.items()}
    check(delta["kernels/paged_attn_programs"] >= 1,
          "no program was built on the paged-attention kernel")
    check(delta["kernels/paged_attn_dense_programs"] == 0,
          "a program was built on the dense gather path")
    check(delta["compile/degraded"] == 0, "a compiled program is degraded")
    check(st["step_replays"] == 0 and delta["serve/step_replays"] == 0,
          "the scheduler's fault policy replayed a step")
    if tp:
        check(page_devices == n and page_shard[1] == cfg["heads"] // n,
              f"KV pages are not split over {n} devices: shard "
              f"{page_shard} on {page_devices}")

    # token rule (module docstring): teacher-forced dense forward, one
    # compiled shape for every request
    params = model.params

    def deficits(p, ids, targets):
        logits, _ = model.apply(p, model.state, ids, training=False)
        logits = logits[0].astype(jnp.float32)
        chosen = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - chosen

    deficits = jax.jit(deficits)
    worst, exact, total = 0.0, 0, 0
    for (prompt, new), got in zip(plan, outputs):
        seq = np.zeros((L + 1,), np.int32)
        seq[:prompt.size + new] = np.concatenate([prompt, got])
        d = np.asarray(deficits(params, jnp.asarray(seq[None, :L]),
                                jnp.asarray(seq[1:])))
        d = d[prompt.size - 1:prompt.size - 1 + new]
        worst = max(worst, float(d.max()))
        exact += int((d == 0).sum())
        total += new
    check(worst <= env.logit_tol,
          f"a served token sits {worst:.3e} below the reference maximum "
          f"(tolerance {env.logit_tol:g})")

    generate = []
    order = np.argsort([p.size for p, _ in plan])
    picks = order[np.linspace(0, len(order) - 2,
                              cfg["generate_checks"]).astype(int)]
    for i in picks:
        prompt, new = plan[i]
        ref = np.asarray(jax.jit(lambda p, x, new=new: model.generate(
            p, x, max_new_tokens=new))(params, jnp.asarray(prompt[None])))
        ref = ref[0, prompt.size:]
        same = ref == outputs[i]
        generate.append({
            "prompt": int(prompt.size), "new": new,
            "equal": bool(same.all()),
            "first_divergence": None if same.all() else int(same.argmin())})
    return {"placement": "tp" if tp else "single", "devices": n if tp else 1,
            "slots": cfg["slots"], "block_size": cfg["block_size"],
            "max_seq_len": L, "prefill_chunk": cfg["prefill_chunk"],
            "requests": len(plan),
            "prompt_tokens": [int(p.size) for p, _ in plan],
            "new_tokens": [m for _, m in plan],
            "warmup_programs": warm_n, "warmup_compile_s": round(warm_s, 1),
            "warmup_wall_s": round(t_warm, 1),
            "serve_wall_s": round(t_serve, 1),
            "tokens_served": total, "decode_steps": st["decode_steps"],
            "prefill_chunks": st["prefill_chunks"],
            "compiles_after_warmup": late_n,
            "page_shard": page_shard, "page_devices": page_devices,
            "tokens_at_reference_argmax": f"{exact}/{total}",
            "worst_logit_deficit": worst, "logit_tol": env.logit_tol,
            "generate": generate, "counters": delta,
            "kv_blocks_in_use_after": st["kv"]["blocks_in_use"],
            "bytes_in_use": mem}


# --------------------------------------------------------------------- main

class Env:
    """What a phase needs to know about where it runs."""

    def __init__(self, platform, rehearsal):
        self.platform = platform
        self.rehearsal = rehearsal
        self.interpret = rehearsal
        # the chip multiplies f32 operands in bf16 passes; the CPU
        # interpreter is f32 throughout
        self.kernel_tol = 1e-4 if rehearsal else 2e-2
        self.logit_tol = 1e-3 if rehearsal else 2e-2

    def say(self, msg):
        tag = "REHEARSAL " if self.rehearsal else ""
        print(f"chip_smoke: {tag}{msg}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="explicit CPU rehearsal: tiny sizes, kernels in "
                         "interpret mode")
    ap.add_argument("--phases", default=None,
                    help="comma list; default: every phase the device "
                         "count calls for")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["BIGDL_TPU_FLASH"] = "interpret"
        os.environ["BIGDL_TPU_PAGED_ATTN"] = "interpret"
    sys.path.insert(0, _HERE)
    import jax
    try:
        import bigdl_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke: cannot import bigdl_tpu from {_HERE} "
                         f"({e}) - run it from the root of a checkout")
    import jaxlib
    devices = jax.devices()
    dev = devices[0]
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    env = Env(dev.platform, args.rehearse)
    say = env.say
    say(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if args.rehearse:
        check(dev.platform == "cpu", "a rehearsal runs on the CPU")
    elif dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: platform is {dev.platform!r} ({dev.device_kind}), "
            "not 'tpu' - this script passes on the chip or fails; "
            "`--rehearse` is the explicit CPU rehearsal")
    check(len(devices) in (1, 2, 4, 8),
          f"{len(devices)} devices: the multi-device phases split 16 heads")

    from bigdl_tpu import observability as obs
    from bigdl_tpu.utils import engine
    obs.enable()        # the counters this script reads are gated on it
    cache_dir = engine.maybe_enable_compilation_cache()
    say(f"compile cache: {cache_dir} "
        f"({engine.compilation_cache_entries()} entries at start)")
    cfg = TINY if args.rehearse else FULL
    watch = CompileWatch()

    phases = [("kernels", lambda: phase_kernels(cfg, env)),
              ("trainer", lambda: phase_trainer(cfg, env)),
              ("server", lambda: phase_server(cfg, env, watch))]
    if len(devices) > 1:
        phases += [
            ("trainer_zero1", lambda: phase_trainer(cfg, env, "zero1")),
            ("server_tp", lambda: phase_server(cfg, env, watch, tp=True))]
    if args.phases:
        wanted = args.phases.split(",")
        unknown = set(wanted) - {n for n, _ in phases}
        check(not unknown, f"unknown phase(s) {sorted(unknown)}; this "
                           f"device count has {[n for n, _ in phases]}")
        phases = [(n, f) for n, f in phases if n in wanted]

    t_all = time.perf_counter()
    for name, fn in phases:
        mark, (h0, m0) = watch.mark(), cache_counts()
        t0 = time.perf_counter()
        report = fn()
        wall = time.perf_counter() - t0
        n_prog, compile_s, _ = watch.since(mark)
        h1, m1 = cache_counts()
        say(f"phase {name}: ok wall={wall:.1f}s programs={n_prog} "
            f"compile={compile_s:.1f}s cache_hits={h1 - h0} "
            f"cache_misses={m1 - m0}")
        print(json.dumps({"phase": name, "rehearsal": args.rehearse,
                          "wall_s": round(wall, 1), "programs": n_prog,
                          "compile_s": round(compile_s, 1),
                          "cache_hits": h1 - h0, "cache_misses": m1 - m0,
                          **report}), flush=True)
        gc.collect()    # drop the phase's device arrays before the next
    hits, misses = cache_counts()
    say(f"all {len(phases)} phase(s) passed in "
        f"{time.perf_counter() - t_all:.1f}s; compile cache hits={hits} "
        f"misses={misses} ({engine.compilation_cache_entries()} entries)")
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.rehearse:
        result = {"rehearsal": True, **result}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
