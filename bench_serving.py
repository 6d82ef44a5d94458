#!/usr/bin/env python3
"""Closed-loop serving microbench: dynamic batching vs per-request dispatch.

N client threads each submit one request, wait for its result, and
immediately submit the next (closed loop) — the arrival process real
concurrent users generate. Two arms over the SAME compiled forward
(``optim.predictor.shared_forward``, so the comparison isolates
batching, not compilation):

* **per-request** — every client calls ``PredictionService.predict()``
  on its own 1-sample batch: the RPC-per-inference pattern, and the
  only online path that existed before the engine. (A third context
  line measures the raw pre-warmed 1-sample jit dispatch — the floor a
  zero-envelope RPC server could reach; batching must beat the real
  API by 3x, and the bench records how much of that is envelope vs
  dispatch.)
* **batched** — clients go through :class:`bigdl_tpu.serving.ServingEngine`;
  the batcher coalesces concurrent requests into padded shape-bucket
  micro-batches.

Reports throughput (req/s), mean batch occupancy, p50/p99 latency (from
the ``serve/latency_ms`` histogram), rejected/timeout counts — and
rides ``BENCH_METRICS.json`` with the training bench lines
(``BENCH_METRICS_OUT`` overrides the path, '' disables).

LM mode (``--lm``) benches AUTOREGRESSIVE serving instead: a
mixed-length closed-loop decode load (heterogeneous prompt lengths and
generation budgets) over the continuous-batching
:class:`bigdl_tpu.serving.DecodeScheduler`, versus WHOLE-REQUEST
batching (the same scheduler in ``admission="static"`` mode: a batch
admits, runs every member's full generation, drains, then the next
batch forms — the pre-iteration-level serving discipline). Identical
compiled kernels, identical requests — the arms isolate the
scheduling policy. Reports ``serve/tokens_per_s``, TTFT p50/p99 and
TPOT per arm (from the per-request trace dicts), and the
continuous-vs-static ratios the perf gate pins.

Router mode (``--router``) benches the SLO story (ISSUE 10): a mixed
deadline-class load — tight-deadline interactive clients next to
loose-deadline bulk clients — over TWO arms at the same offered load:

* **single-queue baseline** — ONE ServingEngine, every client FIFO
  through its queue: tight requests wait behind bulk ones exactly when
  load is high (the regime the router exists for).
* **router** — 2 engine replicas behind
  :class:`bigdl_tpu.serving.Router` with weighted-fair priority classes
  (tight 8 : bulk 1), deadline-aware least-loaded placement and
  fail-fast doomed admission. Replica queues are kept SHALLOW so
  backpressure lands in the router where class priority can act
  (docs/SERVING.md "Router").

Reports per-class p50/p99 latency, deadline misses, and GOODPUT
(requests answered WITHIN their deadline per second); the acceptance
ratios the perf gate pins are tight-class p99 (baseline/router, > 1 =
router better), total goodput (router/single-replica, the >= 1.5x
claim), and zero tight-class misses through the router at the pinned
load point.

Run:
  JAX_PLATFORMS=cpu python bench_serving.py            # 16 clients
  JAX_PLATFORMS=cpu python bench_serving.py --smoke    # make serve-smoke
  JAX_PLATFORMS=cpu python bench_serving.py --lm       # LM decode bench
  JAX_PLATFORMS=cpu python bench_serving.py --lm --smoke
  JAX_PLATFORMS=cpu python bench_serving.py --router   # SLO router bench
  JAX_PLATFORMS=cpu python bench_serving.py --router --smoke

Env knobs: SERVE_CLIENTS, SERVE_REQUESTS (per client), SERVE_MAX_BATCH,
SERVE_MAX_WAIT_MS, SERVE_DEADLINE_MS; LM mode: SERVE_LM_CLIENTS,
SERVE_LM_REQUESTS, SERVE_LM_SLOTS; router mode: SERVE_RT_TIGHT_RPS /
SERVE_RT_BULK_RPS (offered load), SERVE_RT_SECONDS (generation
window), SERVE_RT_TIGHT_MS / SERVE_RT_BULK_MS (deadline tiers),
SERVE_RT_REPLICAS.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


def _platform() -> str:
    """The platform this process's JAX actually runs on — what every
    in-process result line reports as its ``backend``."""
    import jax
    return jax.default_backend()


#: fleet agents are separate processes, each importing JAX on its own; N
#: agents cannot share one chip, so the cross-process fleet benches are
#: CPU drills: agents are pinned to this platform and their lines say so
_FLEET_AGENT_PLATFORM = "cpu"


def _build_model():
    from bigdl_tpu.models.lenet import LeNet5
    model = LeNet5()
    model.ensure_initialized()
    return model


def _client_pool(n_clients, fn):
    """Run ``fn(client_id)`` on n threads; returns wall seconds."""
    errs = []

    def run(i):
        try:
            fn(i)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)
    ts = [threading.Thread(target=run, args=(i,), name=f"client-{i}")
          for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    if errs:
        raise errs[0]
    return dt


def bench_serving(n_clients: int, n_requests: int, max_batch: int,
                  max_wait_ms: float, deadline_ms: float):
    from bigdl_tpu import observability as obs
    from bigdl_tpu.optim.predictor import shared_forward
    from bigdl_tpu.optim.staging import place_host_value
    from bigdl_tpu.serving import ServingEngine

    obs.enable()
    model = _build_model()
    fwd = shared_forward(model)
    rng = np.random.RandomState(0)
    samples = rng.randn(n_clients, 784).astype(np.float32)
    total = n_clients * n_requests

    # reference outputs: one dispatch over all client samples
    want = np.asarray(fwd(model.params, model.state,
                          place_host_value(samples)))

    # -- arm 1: per-request predict() with its API defaults — the
    # pre-engine serving path, envelope and all (dataset wrap + a stager
    # thread spawned PER CALL). The raw-dispatch arm below is the
    # zero-envelope floor, so the split between envelope cost and
    # dispatch cost is visible in the recorded lines.
    from bigdl_tpu.optim.predictor import PredictionService
    svc = PredictionService(model)
    svc.predict(samples[:1])  # warm the 1-sample bucket

    def per_request(i):
        x = samples[i:i + 1]
        for _ in range(n_requests):
            svc.predict(x)
    dt_per_req = _client_pool(n_clients, per_request)

    # -- context: raw pre-warmed 1-sample dispatch (no predict envelope)
    np.asarray(fwd(model.params, model.state,
                   place_host_value(samples[:1])))

    def raw_dispatch(i):
        x = place_host_value(samples[i:i + 1])
        for _ in range(n_requests):
            np.asarray(fwd(model.params, model.state, x))
    dt_raw = _client_pool(n_clients, raw_dispatch)

    # -- arm 2: engine (warmup compiles every bucket before traffic) ----
    engine = ServingEngine(model, input_shape=(784,), max_batch=max_batch,
                           max_wait_ms=max_wait_ms,
                           max_queue=max(4 * n_clients, 64),
                           default_deadline_ms=deadline_ms)
    reg = obs.registry()
    outputs = [None] * n_clients
    with engine:
        def batched(i):
            for _ in range(n_requests):
                outputs[i] = engine.submit(samples[i]).result(
                    timeout=deadline_ms / 1000.0 + 30.0)
        dt_batched = _client_pool(n_clients, batched)
        engine.drain(timeout=30.0)
        st = engine.stats()

    # every client's steady-state answer must match the direct forward.
    # Tight-tolerance, not bitwise: padding rows is bitwise-invariant
    # (tests/test_serving.py asserts that), but DIFFERENT bucket shapes
    # may legitimately differ in the last ulp (XLA picks per-shape conv
    # algorithms — measured 2.4e-7 between the [1,...] and [16,...]
    # LeNet executables on CPU)
    bad = sum(1 for i in range(n_clients)
              if not np.allclose(outputs[i], want[i], rtol=1e-5, atol=1e-6))
    lat = reg.get("serve/latency_ms")
    occ = reg.get("serve/batch_occupancy")
    # per-request stage decomposition: where does the p99 actually go —
    # the batching window (queue_wait), host stacking (assemble), or
    # the device round-trip (dispatch)?
    stages = {name: reg.get(f"serve/{name}_ms")
              for name in ("queue_wait", "assemble", "dispatch")}
    stage_p99 = {name: (round(h.quantile(0.99), 3) if h else 0.0)
                 for name, h in stages.items()}
    dropped = total - st["completed"]
    thr_batched = total / dt_batched
    thr_per_req = total / dt_per_req
    lines = [{
        "metric": "serving_batched_req_per_s",
        "value": round(thr_batched, 1), "unit": "req/s",
        "clients": n_clients, "requests": total,
        "max_batch": max_batch, "max_wait_ms": max_wait_ms,
        "deadline_ms": deadline_ms,
        "batch_occupancy_mean": round(occ.mean, 3) if occ else 0.0,
        "batches": st["batches"],
        "latency_p50_ms": round(lat.quantile(0.5), 3) if lat else 0.0,
        "latency_p99_ms": round(lat.quantile(0.99), 3) if lat else 0.0,
        "queue_wait_p99_ms": stage_p99["queue_wait"],
        "assemble_p99_ms": stage_p99["assemble"],
        "dispatch_p99_ms": stage_p99["dispatch"],
        "rejected": st["rejected"], "timeouts": st["timeouts"],
        "dropped": dropped, "mismatches": bad,
        "backend": _platform(),
    }, {
        "metric": "serving_per_request_req_per_s",
        "value": round(thr_per_req, 1), "unit": "req/s",
        "clients": n_clients, "requests": total,
        "backend": _platform(),
    }, {
        "metric": "serving_raw_dispatch_req_per_s",
        "value": round(total / dt_raw, 1), "unit": "req/s",
        "clients": n_clients, "requests": total,
        "backend": _platform(),
    }, {
        "metric": "serving_batching_speedup",
        "value": round(thr_batched / thr_per_req, 2), "unit": "x",
        "clients": n_clients,
        "backend": _platform(),
    }]
    return lines, st, bad, dropped


def _build_lm_model():
    from bigdl_tpu.models.transformer_lm import TransformerLM
    model = TransformerLM(vocab_size=128, hidden_size=64, num_heads=4,
                          filter_size=128, num_layers=2, max_len=512)
    model.ensure_initialized()
    return model


def _lm_workload(n_clients, n_requests, max_seq_len, seed=0):
    """Deterministic mixed-length request plan: client i's request j has
    its own (prompt, max_new) — short chats next to long-context
    queries, the mix whole-request batching serves worst."""
    rng = np.random.RandomState(seed)
    plan = []
    for i in range(n_clients):
        reqs = []
        for _ in range(n_requests):
            tp = int(rng.randint(4, 49))
            mn = int(rng.randint(4, 33))
            reqs.append((rng.randint(1, 128, size=tp).astype(np.int32), mn))
        plan.append(reqs)
    return plan


def _paged_attn_env(value):
    """Pin the paged-attention dispatch mode for one arm (the knob is
    read at trace time, so it must be set around scheduler build +
    warmup). ``None`` restores the ambient default."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        old = os.environ.get("BIGDL_TPU_PAGED_ATTN")
        if value is None:
            os.environ.pop("BIGDL_TPU_PAGED_ATTN", None)
        else:
            os.environ["BIGDL_TPU_PAGED_ATTN"] = value
        try:
            yield
        finally:
            if old is None:
                os.environ.pop("BIGDL_TPU_PAGED_ATTN", None)
            else:
                os.environ["BIGDL_TPU_PAGED_ATTN"] = old
    return ctx()


def _run_lm_arm(model, plan, admission, max_slots, paged_attn="off",
                draft_model=None, spec_k=4):
    """One closed-loop run over ``plan``; returns (tokens/s, ttft list,
    tpot list, stats, outputs keyed (client, request)). A warmup pass
    first compiles every bucket/chunk shape so the timed window
    measures scheduling, not XLA. ``paged_attn`` pins the attention
    path for the arm (the kernel A/B lever); ``draft_model`` arms the
    batched speculative path (the spec A/B lever). The prefix cache is
    OFF in these arms: the workload's random prompts never hit, so
    leaving it on would fold pure admission-hash/registration overhead
    into the continuous-vs-static numbers these arms exist to isolate —
    the shared-prefix arm below measures the cache on the workload it
    serves."""
    from bigdl_tpu.serving import DecodeScheduler
    with _paged_attn_env(paged_attn):
        sched = DecodeScheduler(
            model, max_slots=max_slots, block_size=16,
            max_seq_len=max(96, max(int(p.size) + mn + 2 + spec_k + 1
                                    for reqs in plan for p, mn in reqs)),
            prefill_chunk=16, admission=admission, prefix_cache=False,
            draft_model=draft_model, spec_k=spec_k)
        n_clients = len(plan)
        total_tokens = [0] * n_clients
        ttfts, tpots = [], []
        outputs = {}
        lock = threading.Lock()
        with sched:  # start() precompiles every dispatchable shape
            def client(i):
                for j, (prompt, max_new) in enumerate(plan[i]):
                    fut = sched.submit(prompt, max_new)
                    out = fut.result(timeout=300)
                    with lock:
                        total_tokens[i] += int(out.size)
                        outputs[(i, j)] = np.asarray(out)
                        if fut.trace:
                            if fut.trace.get("ttft_ms") is not None:
                                ttfts.append(fut.trace["ttft_ms"])
                            if fut.trace.get("tpot_ms"):
                                tpots.append(fut.trace["tpot_ms"])
            dt = _client_pool(n_clients, client)
            sched.drain(timeout=60.0)
            st = sched.stats()
    return sum(total_tokens) / dt, ttfts, tpots, st, outputs


def _pct(xs, q):
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.999999))]


def bench_serving_lm(n_clients, n_requests, max_slots):
    model = _build_lm_model()
    plan = _lm_workload(n_clients, n_requests, 512)
    total = n_clients * n_requests
    # static (whole-request) first, then continuous — same model
    # instance, each arm warms its own compiled shapes before timing.
    # Both baseline arms PIN the dense attention path so the kernel A/B
    # below isolates the attention implementation, not the backend's
    # auto policy.
    thr_s, ttft_s, tpot_s, st_s, _ = _run_lm_arm(model, plan, "static",
                                                 max_slots)
    thr_c, ttft_c, tpot_c, st_c, out_c = _run_lm_arm(model, plan,
                                                     "continuous",
                                                     max_slots)
    # kernel A/B arm (ISSUE 11): continuous batching with the Pallas
    # paged-attention kernel — compiled on TPU-class backends, the
    # interpreter on CPU (functionally the same kernel; interpret-mode
    # tokens/s is a CORRECTNESS number, not a perf claim — the HBM win
    # only exists where there is HBM, which is why kernel_mode rides
    # the line). Tokens must match the dense arm bitwise.
    backend = _platform()
    kernel_mode = "on" if backend == "tpu" else "interpret"
    # trace-count spy (same discipline as the tests and kernels_smoke):
    # a dense-path number published as kernel_mode 'on' would be a
    # silent-provenance failure — the arm must PROVE the Pallas path
    # built its programs
    from bigdl_tpu.kernels import paged_attention as _pk
    traces0 = _pk.trace_count()
    thr_k, ttft_k, tpot_k, st_k, out_k = _run_lm_arm(
        model, plan, "continuous", max_slots, paged_attn=kernel_mode)
    kernel_traced = _pk.trace_count() > traces0
    match = (len(out_c) == len(out_k)
             and all(np.array_equal(out_c[key], out_k[key])
                     for key in out_c))
    lines = [{
        "metric": "serving_lm_tokens_per_s",
        "value": round(thr_c, 1), "unit": "tok/s",
        "clients": n_clients, "requests": total, "max_slots": max_slots,
        "decode_steps": st_c["decode_steps"],
        "backend": _platform(),
    }, {
        "metric": "serving_lm_ttft_p50_ms",
        "value": round(_pct(ttft_c, 0.5), 2), "unit": "ms",
        "clients": n_clients, "backend": _platform(),
    }, {
        "metric": "serving_lm_ttft_p99_ms",
        "value": round(_pct(ttft_c, 0.99), 2), "unit": "ms",
        "clients": n_clients, "backend": _platform(),
    }, {
        "metric": "serving_lm_tpot_ms",
        "value": round(sum(tpot_c) / max(len(tpot_c), 1), 3),
        "unit": "ms", "clients": n_clients, "backend": _platform(),
    }, {
        "metric": "serving_lm_static_tokens_per_s",
        "value": round(thr_s, 1), "unit": "tok/s",
        "clients": n_clients, "requests": total, "max_slots": max_slots,
        "backend": _platform(),
    }, {
        "metric": "serving_lm_static_ttft_p99_ms",
        "value": round(_pct(ttft_s, 0.99), 2), "unit": "ms",
        "clients": n_clients, "backend": _platform(),
    }, {
        "metric": "serving_lm_cb_speedup",
        "value": round(thr_c / max(thr_s, 1e-9), 2), "unit": "x",
        "clients": n_clients, "backend": _platform(),
    }, {
        "metric": "serving_lm_ttft_p99_ratio",
        "value": round(_pct(ttft_s, 0.99) / max(_pct(ttft_c, 0.99), 1e-9),
                       2), "unit": "x",
        "clients": n_clients, "backend": _platform(),
    }, {
        "metric": "serving_lm_kernel_tokens_per_s",
        "value": round(thr_k, 1), "unit": "tok/s",
        "clients": n_clients, "requests": total, "max_slots": max_slots,
        "decode_steps": st_k["decode_steps"],
        "kernel_mode": kernel_mode, "kernel_traced": kernel_traced,
        "backend": backend,
    }, {
        "metric": "serving_lm_kernel_vs_dense",
        "value": round(thr_k / max(thr_c, 1e-9), 2), "unit": "x",
        "kernel_mode": kernel_mode, "clients": n_clients,
        "backend": backend,
    }, {
        # the bench-level bitwise gate: every request's kernel-arm
        # tokens equal its dense-arm tokens (1.0 or the run fails)
        "metric": "serving_lm_kernel_token_match",
        "value": 1.0 if match else 0.0, "unit": "frac",
        "requests": total, "kernel_mode": kernel_mode,
        "backend": backend,
    }]
    return lines, st_c, st_s, st_k


def _build_spec_pair(num_layers=12, hidden=192, heads=4, filt=768):
    """Target + cheap draft with CONTRIVED total agreement: the
    target's embedding/head/final-LN and first block ARE the draft's,
    and every deeper target block's residual contributions (attn.wo,
    ffn.w2/b2) are zeroed — those blocks still RUN (the verify pays the
    full deep-model cost) but contribute exactly +0.0 to the residual
    stream, so target logits are bitwise the draft's and greedy
    acceptance is total. That isolates the SCHEDULING claim this arm
    pins — one cheap draft burst + one batched verify amortizing the
    expensive model's weight stream over spec_k+1 tokens per row —
    at a realistic ~num_layers:1 target/draft cost ratio, without
    training a real draft. (Acceptance on real model pairs is a model-
    quality property; the serving tier's job, measured here, is to
    convert whatever acceptance exists into fewer dispatches. Mean
    acceptance length is reported so the telemetry pipeline is the one
    operators will read.)"""
    import jax.numpy as jnp
    from bigdl_tpu.models.transformer_lm import TransformerLM
    cfg = dict(vocab_size=128, hidden_size=hidden, num_heads=heads,
               filter_size=filt, max_len=512)
    target = TransformerLM(num_layers=num_layers, **cfg)
    target.ensure_initialized()
    draft = TransformerLM(num_layers=1, **cfg)
    draft.ensure_initialized()
    p = {"embed": draft.params["embed"], "ln_f": draft.params["ln_f"],
         "block0": draft.params["block0"]}
    for i in range(1, num_layers):
        blk = {k: dict(v) for k, v in target.params[f"block{i}"].items()}
        blk["attn"]["wo"] = jnp.zeros_like(blk["attn"]["wo"])
        blk["ffn"]["w2"] = jnp.zeros_like(blk["ffn"]["w2"])
        blk["ffn"]["b2"] = jnp.zeros_like(blk["ffn"]["b2"])
        p[f"block{i}"] = blk
    target.params = p
    return target, draft


def bench_serving_lm_spec(n_clients, n_requests, max_slots, spec_k=6,
                          smoke=False):
    """Batched-speculation A/B arm (ISSUE 14): the SAME multi-request
    continuous-batching load served twice — plain, then with the draft
    armed so every greedy row rides the batched draft/verify rounds.
    Both arms run >= 4 concurrent closed-loop clients (speculation
    under continuous batching is the point; the PR-8 fast path only
    ever engaged solo). Reports tokens/s per arm, the spec/plain ratio
    (the acceptance bar: > 1), the mean per-row acceptance length
    (``spec_accepted / spec_row_rounds`` — the telemetry operators use
    to size spec_k), and enforces spec tokens bitwise == plain tokens
    at every scale (speculation is output-preserving or it is
    broken). The smoke pair is tiny (the smoke run checks plumbing +
    the bitwise gate, never the ratio — a 12-layer warmup pays real
    XLA time tier-1 shouldn't).

    The pinned operating point is 4 clients over 4 slots: speculation's
    CPU-measurable win is dispatch/gemm-efficiency amortization (a
    (4, k+1) verify runs the MXU-shaped gemms a 4-row step wastes), and
    at deeper batches the plain arm's gemms are already efficient so
    the CPU proxy shrinks toward FLOP parity — the weight re-stream win
    the ratio proxies lives where there is HBM (the on-chip A/B is the
    ROADMAP follow-up, same caveat as the kernel arm's interpret
    numbers)."""
    target, draft = (_build_spec_pair(num_layers=2, hidden=64, filt=128)
                     if smoke else _build_spec_pair())
    # longer generations than the cb-vs-static plan: speculation
    # amortizes DECODE dispatches, so decode must dominate prefill —
    # and enough of them that the timed window is not noise-dominated
    if not smoke:
        n_requests = max(n_requests, 6)
    rng = np.random.RandomState(7)
    plan = []
    for i in range(n_clients):
        reqs = []
        for _ in range(n_requests):
            tp = int(rng.randint(4, 33))
            mn = int(rng.randint(32, 65))
            reqs.append((rng.randint(1, 128, size=tp).astype(np.int32),
                         mn))
        plan.append(reqs)
    thr_p, _, _, st_p, out_p = _run_lm_arm(target, plan, "continuous",
                                           max_slots, spec_k=spec_k)
    thr_s, _, _, st_s, out_s = _run_lm_arm(target, plan, "continuous",
                                           max_slots, draft_model=draft,
                                           spec_k=spec_k)
    match = (len(out_p) == len(out_s)
             and all(np.array_equal(out_p[key], out_s[key])
                     for key in out_p))
    accept_mean = st_s["spec_accepted"] / max(st_s["spec_row_rounds"], 1)
    lines = [{
        "metric": "serving_lm_spec_tokens_per_s",
        "value": round(thr_s, 1), "unit": "tok/s",
        "clients": n_clients, "requests": n_clients * n_requests,
        "max_slots": max_slots, "spec_k": spec_k,
        "spec_rounds": st_s["spec_rounds"],
        "decode_steps": st_s["decode_steps"],
        "backend": _platform(),
    }, {
        "metric": "serving_lm_spec_plain_tokens_per_s",
        "value": round(thr_p, 1), "unit": "tok/s",
        "clients": n_clients, "decode_steps": st_p["decode_steps"],
        "backend": _platform(),
    }, {
        "metric": "serving_lm_spec_tokens_per_s_vs_plain",
        "value": round(thr_s / max(thr_p, 1e-9), 2), "unit": "x",
        "clients": n_clients, "spec_k": spec_k, "backend": _platform(),
    }, {
        "metric": "serving_lm_spec_accept_len_mean",
        "value": round(accept_mean, 3), "unit": "tokens",
        "spec_k": spec_k, "row_rounds": st_s["spec_row_rounds"],
        "backend": _platform(),
    }, {
        # bench-level bitwise gate (enforced even in smoke): per
        # request, spec-arm tokens == plain-arm tokens
        "metric": "serving_lm_spec_token_match",
        "value": 1.0 if match else 0.0, "unit": "frac",
        "requests": n_clients * n_requests, "backend": _platform(),
    }]
    return lines, st_s, st_p


def bench_serving_lm_prefix(n_clients, n_requests, prefix_len, max_slots):
    """Shared-system-prompt arm (ISSUE 12): every prompt opens with ONE
    shared ``prefix_len``-token prefix (the system-prompt shape that
    dominates production traffic). A single synchronous COLD request
    seeds the prefix cache and measures the TTFT every request would
    pay without sharing; the closed-loop swarm that follows hits the
    cache — admission adopts the resident blocks and skips their
    prefill, so warm TTFT collapses to the tail chunk + first decode
    step and the prefix is stored once. Reported: hit rate, the
    fraction of prefill FLOPs the cache absorbed (reused / total prompt
    tokens — prefill cost is linear in tokens at fixed chunking), and
    the warm/cold TTFT ratio (the headline; < 0.5 is the acceptance
    bar on measured runs)."""
    from bigdl_tpu.serving import DecodeScheduler
    model = _build_lm_model()
    rng = np.random.RandomState(42)
    prefix = rng.randint(1, 128, size=prefix_len).astype(np.int32)
    plan = []
    for i in range(n_clients):
        reqs = []
        for _ in range(n_requests):
            sfx = rng.randint(1, 128, size=int(rng.randint(4, 17)))
            reqs.append((np.concatenate([prefix, sfx.astype(np.int32)]),
                         int(rng.randint(8, 17))))
        plan.append(reqs)
    with _paged_attn_env("off"):
        sched = DecodeScheduler(
            model, max_slots=max_slots, block_size=16,
            max_seq_len=prefix_len + 64, prefill_chunk=16)
        with sched:
            seed_prompt, seed_mn = plan[0][0]
            cold_fut = sched.submit(seed_prompt, seed_mn)
            cold_fut.result(timeout=300)
            cold_ttft = cold_fut.trace["ttft_ms"]
            warm_ttfts = []
            prompt_tokens = [int(seed_prompt.size)]
            lock = threading.Lock()

            def client(i):
                for j, (p, mn) in enumerate(plan[i]):
                    if i == 0 and j == 0:
                        continue          # the seed request already ran
                    fut = sched.submit(p, mn)
                    fut.result(timeout=300)
                    with lock:
                        prompt_tokens.append(int(p.size))
                        tr = fut.trace or {}
                        if tr.get("ttft_ms") is not None \
                                and tr.get("prefix_hit_tokens"):
                            warm_ttfts.append(tr["ttft_ms"])
            _client_pool(n_clients, client)
            sched.drain(timeout=60.0)
            st = sched.stats()
    admitted = st["prefix_hits"] + st["prefix_misses"]
    hit_rate = st["prefix_hits"] / max(admitted, 1)
    saved_frac = st["prefix_reused_tokens"] / max(sum(prompt_tokens), 1)
    warm_p50 = _pct(warm_ttfts, 0.5)
    ratio = warm_p50 / max(cold_ttft, 1e-9)
    lines = [{
        "metric": "serving_lm_prefix_hit_rate",
        "value": round(hit_rate, 4), "unit": "frac",
        "clients": n_clients, "requests": admitted,
        "prefix_len": prefix_len, "backend": _platform(),
    }, {
        "metric": "serving_lm_prefix_prefill_saved_frac",
        "value": round(saved_frac, 4), "unit": "frac",
        "reused_tokens": st["prefix_reused_tokens"],
        "prompt_tokens": sum(prompt_tokens), "backend": _platform(),
    }, {
        "metric": "serving_lm_prefix_cold_ttft_ms",
        "value": round(cold_ttft, 2), "unit": "ms",
        "prefix_len": prefix_len, "backend": _platform(),
    }, {
        "metric": "serving_lm_prefix_warm_ttft_p50_ms",
        "value": round(warm_p50, 2), "unit": "ms",
        "warm_requests": len(warm_ttfts), "backend": _platform(),
    }, {
        # the headline: warm TTFT as a fraction of cold (lower=better;
        # the acceptance bar is < 0.5 on measured runs)
        "metric": "serving_lm_prefix_warm_cold_ttft_ratio",
        "value": round(ratio, 3), "unit": "x",
        "prefix_len": prefix_len, "clients": n_clients, "backend": _platform(),
    }]
    return lines, st


def bench_serving_lm_spill(n_requests, max_slots, smoke):
    """Host-tier arm (ISSUE 18). Phase 1 seeds N distinct prefixes and
    measures their cold TTFTs, then EVICTS every chain — with the host
    pool underneath, eviction spills the pages to host RAM instead of
    dropping the bytes. Phase 2 revisits every prefix: the lookup
    refills the spilled chain through the ordinary warm-hit path (a
    second-chance hit, one batched adopt for the whole chain), so the
    headline is hit-after-spill TTFT over cold TTFT — the refill must
    beat re-running the prefill it replaces. Phase 3 runs a DISJOINT
    prefix rotation closed-loop (each client cycles its own prefixes,
    so a revisit never finds a concurrent twin's resident chain) over
    a device pool deliberately too small for the working set —
    admission pressure evicts chains LIVE — twice: once with the host
    tier under it (evictions spill, revisits refill) and once without
    (evictions drop the bytes, revisits re-prefill) — decode tokens/s
    with swap traffic over tokens/s without the tier. Swaps ride step
    boundaries (the compiled step never blocks on one), so the tier
    must hold near-parity here — on the CPU backend the stager's
    gather and the refill transfer share the ONE device queue with
    decode, so parity is the floor of the TPU case, where swap traffic
    is DMA alongside compute."""
    from bigdl_tpu.serving import DecodeScheduler, blocks_for_tokens
    from bigdl_tpu.serving.kv_cache import SPILL_PENDING
    model = _build_lm_model()
    rng = np.random.RandomState(7)
    bs = 16
    n_prefixes = 6
    prefix_len = 64 if smoke else 448     # block-aligned: the registered
    chain = prefix_len // bs              # chain IS the shared prefix
    prefixes = [rng.randint(1, 128, size=prefix_len).astype(np.int32)
                for _ in range(n_prefixes)]
    sfx = lambda: rng.randint(1, 128, size=8).astype(np.int32)  # noqa: E731
    worst = blocks_for_tokens(prefix_len + 8 + 16, bs)
    # TTFT pair runs UNCONSTRAINED (all chains + in-flight requests fit:
    # the measured revisits isolate refill vs re-prefill, with no
    # admission-pressure eviction noise); phase 3 runs the tight pool
    roomy_blocks = 1 + n_prefixes * chain + 2 * worst
    # tight pool holds 2 of the 6 chains: each phase-3 client rotates 3
    # disjoint prefixes, so the pool keeps spilling the coldest chain
    # and refilling it two requests later — steady churn, not a
    # 100%-miss antagonist
    tight_blocks = 1 + 2 * chain + 2 * worst
    host_blocks = 2 * n_prefixes * chain + 16

    def settle_spills(sched, deadline_s=30.0):
        """Spills are async: wait for every spilled handle to stage so a
        revisit's refill can't race its own fetch (a PENDING handle is a
        deliberate miss, not a wait — see KVSwapManager.refill)."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            with sched.prefix._lock:
                pending = [h for h, _ in sched.prefix._spilled.values()
                           if h.state == SPILL_PENDING]
            if not pending:
                return
            time.sleep(0.005)

    with _paged_attn_env("off"):
        sched = DecodeScheduler(
            model, max_slots=max_slots, block_size=bs,
            max_seq_len=prefix_len + 64, prefill_chunk=16,
            num_blocks=roomy_blocks, host_blocks=host_blocks)
        with sched:
            cold_ttfts, hit_ttfts = [], []
            for p in prefixes:               # phase 1: clean cold TTFTs
                fut = sched.submit(np.concatenate([p, sfx()]), 8)
                fut.result(timeout=300)
                cold_ttfts.append(fut.trace["ttft_ms"])
            # spill EVERY chain (LRU eviction → host tier), then one
            # throwaway revisit: the first refill pays the staging
            # ring's build + compile, which is warmup, not swap cost
            sched.prefix.evict(n_prefixes * chain)
            settle_spills(sched)
            fut = sched.submit(np.concatenate([prefixes[0], sfx()]), 8)
            fut.result(timeout=300)
            for p in prefixes[1:]:           # phase 2: second-chance hits
                settle_spills(sched)
                h0 = sched.stats()["prefix"]["hits_after_spill"]
                fut = sched.submit(np.concatenate([p, sfx()]), 8)
                fut.result(timeout=300)
                if sched.stats()["prefix"]["hits_after_spill"] > h0:
                    hit_ttfts.append(fut.trace["ttft_ms"])
            sched.drain(timeout=60.0)
            st = sched.stats()

    def thr_arm(**sched_kw):                 # phase 3: decode under churn
        thr_reqs = 4 if smoke else 12
        plan = []
        for i in range(2):   # client i rotates its OWN 3 prefixes
            reqs = []
            for j in range(thr_reqs):
                p = prefixes[3 * i + j % 3]
                reqs.append((np.concatenate([p, sfx()]), 16))
            plan.append(reqs)
        with _paged_attn_env("off"):
            s = DecodeScheduler(model, max_slots=max_slots, block_size=bs,
                                max_seq_len=prefix_len + 64,
                                prefill_chunk=16, **sched_kw)
            total = [0] * len(plan)
            with s:
                def client(i):
                    for p, mn in plan[i]:
                        out = s.submit(p, mn).result(timeout=300)
                        total[i] += int(out.size)
                dt = _client_pool(len(plan), client)
                s.drain(timeout=60.0)
                stt = s.stats()
        return sum(total) / dt, stt

    thr_base, st_base = thr_arm(num_blocks=tight_blocks)  # tier OFF:
    #   evictions drop bytes, every rotation revisit re-prefills
    thr_sp, st_sp = thr_arm(num_blocks=tight_blocks,
                            host_blocks=host_blocks)
    cold_p50, hit_p50 = _pct(cold_ttfts, 0.5), _pct(hit_ttfts, 0.5)
    ratio = hit_p50 / max(cold_p50, 1e-9)
    swap_bytes = (st["host"]["swap_out_bytes"]
                  + st_sp["host"]["swap_out_bytes"])
    lines = [{
        "metric": "serving_lm_spill_cold_ttft_p50_ms",
        "value": round(cold_p50, 2), "unit": "ms",
        "prefix_len": prefix_len, "backend": _platform(),
    }, {
        "metric": "serving_lm_spill_hit_ttft_p50_ms",
        "value": round(hit_p50, 2), "unit": "ms",
        "hits_after_spill": st["prefix"]["hits_after_spill"],
        "spills": st["prefix"]["spills"], "backend": _platform(),
    }, {
        # the headline: a refill from host RAM must undercut the prefill
        # it replaces (lower=better; < 1.0 is the acceptance bar on
        # measured runs)
        "metric": "serving_lm_spill_hit_ttft_ratio",
        "value": round(ratio, 3), "unit": "x",
        "hits_after_spill": st["prefix"]["hits_after_spill"],
        "swap_failures": st["host"]["swap_failures"], "backend": _platform(),
    }, {
        "metric": "serving_lm_kv_swap_out_bytes",
        "value": int(swap_bytes), "unit": "bytes",
        "swap_in_bytes": int(st["host"]["swap_in_bytes"]
                             + st_sp["host"]["swap_in_bytes"]),
        "backend": _platform(),
    }, {
        "metric": "serving_lm_spill_tokens_per_s",
        "value": round(thr_sp, 1), "unit": "tok/s",
        "num_blocks": tight_blocks, "host_blocks": host_blocks,
        "spills": st_sp["prefix"]["spills"], "backend": _platform(),
    }, {
        "metric": "serving_lm_nospill_tokens_per_s",
        "value": round(thr_base, 1), "unit": "tok/s",
        "num_blocks": tight_blocks, "backend": _platform(),
    }, {
        # decode throughput over the SAME tight pool, with the host
        # tier vs without it: the tier converts the rotation's
        # re-prefills into boundary-scheduled refills. Near-parity
        # (~0.95x) is the CPU bar — the stager's gather and the refill
        # transfer share the single CPU device queue with decode, so
        # the swap bandwidth that is free DMA on a TPU is contended
        # compute here; the gate floors the ratio against collapse and
        # the baseline pins the measured band
        "metric": "serving_lm_spill_tokens_per_s_ratio",
        "value": round(thr_sp / max(thr_base, 1e-9), 2), "unit": "x",
        "backend": _platform(),
    }]
    return lines, st, st_sp, st_base


def main_lm(smoke: bool):
    n_clients = int(os.environ.get("SERVE_LM_CLIENTS", 3 if smoke else 8))
    n_requests = int(os.environ.get("SERVE_LM_REQUESTS", 2 if smoke else 4))
    max_slots = int(os.environ.get("SERVE_LM_SLOTS", 4 if smoke else 8))
    prefix_len = int(os.environ.get("SERVE_LM_PREFIX_LEN",
                                    64 if smoke else 256))
    spec_k = int(os.environ.get("SERVE_LM_SPEC_K", 6))
    spec_clients = int(os.environ.get("SERVE_LM_SPEC_CLIENTS", 4))
    spec_slots = int(os.environ.get("SERVE_LM_SPEC_SLOTS", 4))
    lines, st_c, st_s, st_k = bench_serving_lm(n_clients, n_requests,
                                               max_slots)
    sp_lines, st_sp, st_spp = bench_serving_lm_spec(
        spec_clients, n_requests, spec_slots, spec_k=spec_k, smoke=smoke)
    lines += sp_lines
    pf_lines, st_p = bench_serving_lm_prefix(n_clients, n_requests,
                                             prefix_len, max_slots)
    lines += pf_lines
    sl_lines, st_sl, st_sl_thr, st_sl_base = bench_serving_lm_spill(
        n_requests, max_slots, smoke)
    lines += sl_lines
    for line in lines:
        print(json.dumps(line), flush=True)
    _merge_metrics_dump(lines)
    by_metric = {l["metric"]: l for l in lines}
    failures = []
    total = n_clients * n_requests
    for name, st in (("continuous", st_c), ("static", st_s),
                     ("kernel", st_k), ("spec", st_sp),
                     ("spec-plain", st_spp), ("prefix", st_p),
                     ("spill", st_sl), ("spill-thr", st_sl_thr),
                     ("spill-base", st_sl_base)):
        if st["timeouts"]:
            failures.append(f"{st['timeouts']} {name} requests timed out")
        leaked = (st["kv"]["blocks_in_use"]
                  - (st.get("prefix") or {}).get("entries", 0))
        if leaked:
            failures.append(f"{name}: {leaked} KV blocks leaked "
                            "(beyond prefix-cache residency)")
    speedup = by_metric["serving_lm_cb_speedup"]["value"]
    ttft_ratio = by_metric["serving_lm_ttft_p99_ratio"]["value"]
    # the kernel arm's gates hold at EVERY scale, smoke included: the
    # tokens must match the dense arm bitwise AND the Pallas path must
    # actually have served them (a silent dense fallback published as
    # kernel numbers is a provenance lie, not a measurement)
    if by_metric["serving_lm_kernel_token_match"]["value"] != 1.0:
        failures.append("kernel-arm tokens diverged from the dense arm "
                        "(serving_lm_kernel_token_match < 1.0)")
    if not by_metric["serving_lm_kernel_tokens_per_s"]["kernel_traced"]:
        failures.append("kernel arm never traced the Pallas path — its "
                        "numbers are dense-path numbers (fallback?)")
    # the spec arm's gates that hold at EVERY scale, smoke included:
    # speculation is output-preserving (bitwise) or it is broken, and
    # the rounds must actually have run (a spec arm that never
    # speculated is a plain arm wearing the wrong label)
    if by_metric["serving_lm_spec_token_match"]["value"] != 1.0:
        failures.append("spec-arm tokens diverged from the plain arm "
                        "(serving_lm_spec_token_match < 1.0)")
    if by_metric["serving_lm_spec_tokens_per_s"]["spec_rounds"] <= 0:
        failures.append("spec arm never rode a speculative round")
    hit_rate = by_metric["serving_lm_prefix_hit_rate"]["value"]
    warm_ratio = by_metric["serving_lm_prefix_warm_cold_ttft_ratio"]["value"]
    # the prefix arm's HIT accounting holds at every scale, smoke
    # included — a zero hit rate means the cache never engaged and the
    # warm numbers below are cold numbers wearing the wrong label
    if hit_rate <= 0.0:
        failures.append("shared-prefix arm never hit the prefix cache")
    # the spill arm's PROVENANCE gates hold at every scale, smoke
    # included: the tier must actually have spilled (bytes crossed to
    # host), a revisit must have come back as a second-chance hit (or
    # the "hit" TTFTs are cold numbers wearing the wrong label), and
    # no swap may have failed on a healthy run
    spill_hits = by_metric["serving_lm_spill_hit_ttft_ratio"][
        "hits_after_spill"]
    if by_metric["serving_lm_kv_swap_out_bytes"]["value"] <= 0:
        failures.append("spill arm never swapped a block to host RAM")
    if spill_hits <= 0:
        failures.append("spill arm never served a hit-after-spill")
    if by_metric["serving_lm_spill_hit_ttft_ratio"]["swap_failures"]:
        failures.append("spill arm recorded swap failures on a "
                        "fault-free run")
    if not smoke:
        # ISSUE 8 acceptance: continuous batching must beat whole-
        # request batching on BOTH axes (the smoke run is a plumbing
        # check on whatever loaded CI box runs it)
        if speedup < 1.0:
            failures.append(f"continuous tokens/s speedup {speedup}x < 1x")
        if ttft_ratio < 1.0:
            failures.append(f"continuous p99 TTFT ratio {ttft_ratio}x < 1x "
                            "(static had better tail latency)")
        # ISSUE 12 acceptance: a cache hit must skip (nearly) the whole
        # shared prefix's prefill — warm TTFT under half of cold
        if hit_rate < 0.9:
            failures.append(f"prefix hit rate {hit_rate} < 0.9")
        if warm_ratio >= 0.5:
            failures.append(f"warm/cold TTFT ratio {warm_ratio} >= 0.5 "
                            "(prefill-skip bought too little)")
        # ISSUE 14 acceptance: batched speculation must beat the plain
        # continuous arm under multi-request load
        spec_ratio = by_metric[
            "serving_lm_spec_tokens_per_s_vs_plain"]["value"]
        if spec_ratio <= 1.0:
            failures.append(f"batched-spec tokens/s ratio {spec_ratio}x "
                            "<= 1x vs plain continuous batching")
        # ISSUE 18 acceptance: a refill from host RAM must undercut the
        # prefill it replaces (the latency headline), and under the
        # same too-small pool the tier must hold near-parity decode
        # throughput — the floor guards against the swap machinery
        # collapsing the decode loop, while the PERF_BASELINE pin
        # tracks the measured band (on this CPU bench the stager's
        # gather and the refill transfer contend with decode for the
        # one device queue; on a TPU they ride DMA)
        spill_ratio = by_metric["serving_lm_spill_hit_ttft_ratio"]["value"]
        if spill_ratio >= 1.0:
            failures.append(f"hit-after-spill/cold TTFT ratio "
                            f"{spill_ratio}x >= 1x (the refill lost to "
                            "the prefill it replaces)")
        thr_ratio = by_metric["serving_lm_spill_tokens_per_s_ratio"][
            "value"]
        if thr_ratio <= 0.7:
            failures.append(f"decode tokens/s with the host tier "
                            f"{thr_ratio}x <= 0.7x vs the same pool "
                            "without it (swap churn is stalling the "
                            "decode loop, not just paying transfer)")
    if failures:
        print("bench_serving --lm: FAIL — " + "; ".join(failures),
              file=sys.stderr)
        raise SystemExit(1)
    km = by_metric["serving_lm_kernel_tokens_per_s"]
    print(f"bench_serving --lm: ok — "
          f"{by_metric['serving_lm_tokens_per_s']['value']} tok/s "
          f"continuous vs "
          f"{by_metric['serving_lm_static_tokens_per_s']['value']} tok/s "
          f"whole-request ({speedup}x), p99 TTFT "
          f"{by_metric['serving_lm_ttft_p99_ms']['value']}ms vs "
          f"{by_metric['serving_lm_static_ttft_p99_ms']['value']}ms "
          f"({ttft_ratio}x better), TPOT "
          f"{by_metric['serving_lm_tpot_ms']['value']}ms; kernel arm "
          f"({km['kernel_mode']}) {km['value']} tok/s, tokens bitwise "
          f"== dense; spec arm "
          f"{by_metric['serving_lm_spec_tokens_per_s']['value']} tok/s vs "
          f"{by_metric['serving_lm_spec_plain_tokens_per_s']['value']} "
          f"plain "
          f"({by_metric['serving_lm_spec_tokens_per_s_vs_plain']['value']}"
          f"x, mean accept "
          f"{by_metric['serving_lm_spec_accept_len_mean']['value']}), "
          f"tokens bitwise == plain; prefix arm hit rate {hit_rate}, "
          f"warm TTFT "
          f"{by_metric['serving_lm_prefix_warm_ttft_p50_ms']['value']}ms "
          f"vs cold "
          f"{by_metric['serving_lm_prefix_cold_ttft_ms']['value']}ms "
          f"({warm_ratio}x); spill arm {spill_hits} hits-after-spill, "
          f"hit/cold TTFT "
          f"{by_metric['serving_lm_spill_hit_ttft_ratio']['value']}x, "
          f"decode under churn "
          f"{by_metric['serving_lm_spill_tokens_per_s_ratio']['value']}x "
          f"vs tier-off")


# --------------------------------------------------------------- fleet

def _spawn_fleet_agent(fleet_dir, name, role, idx, params_path,
                       model_cfg, sched_cfg):
    """One replica agent subprocess (python -m bigdl_tpu.serving.fleet)."""
    import subprocess
    cfg = {"fleet_dir": fleet_dir, "name": name, "role": role,
           "beat_s": 0.2, "process_index": idx, "model": model_cfg,
           "params_path": params_path, "scheduler": dict(sched_cfg)}
    path = os.path.join(fleet_dir, f"cfg_{name}.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS=_FLEET_AGENT_PLATFORM,
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("BIGDL_TPU_CHAOS", None)
    # agent output goes to FILES, not pipes: nobody drains a pipe while
    # the agent runs, so a chatty agent (jax warnings, death
    # tracebacks) would block on the ~64 KB pipe buffer and wedge
    log = open(os.path.join(fleet_dir, f"agent_{name}.log"), "w")
    return subprocess.Popen(
        [sys.executable, "-m", "bigdl_tpu.serving.fleet", path],
        stdout=log, stderr=subprocess.STDOUT, cwd=repo, env=env)


def _drive_fleet(submit_fn, plan, drain=None):
    """Closed-loop drive of one fleet/router arm: returns
    (tokens_per_s, outputs keyed (client, request), ttft list)."""
    import threading as _t
    n_clients = len(plan)
    total = [0] * n_clients
    outputs, ttfts = {}, []
    lock = _t.Lock()

    def client(i):
        for j, (prompt, max_new) in enumerate(plan[i]):
            fut = submit_fn(prompt, max_new)
            out = fut.result(timeout=600)
            with lock:
                total[i] += int(np.asarray(out).size)
                outputs[(i, j)] = np.asarray(out)
                tr = fut.trace or {}
                if tr.get("ttft_ms") is not None:
                    ttfts.append(tr["ttft_ms"])

    dt = _client_pool(n_clients, client)
    if drain is not None:
        drain(timeout=120.0)
    return sum(total) / dt, outputs, ttfts


def bench_serving_fleet(n_clients, n_requests, max_slots, n_long,
                        smoke=False):
    """ISSUE 15: the cross-process arms.

    Arm A — single-process Router over 2 in-process scheduler replicas
    (the PR-9 configuration) at a closed-loop offered load.
    Arm B — the SAME load through a 2-process fleet (agents in their own
    processes, framed-socket dispatch, file-heartbeat health). The
    tokens must match arm A bitwise (process transparency); tokens/s
    lands as ``serving_fleet_tokens_per_s`` with the fleet/local ratio.
    On a contended CPU box the ratio mostly measures transport + IPC
    tax — the bands are wide; the on-chip numbers are deferred exactly
    like PR 11's kernel arm.
    Arm C — disaggregation: a steady short-request stream rides the
    decode fleet while a burster submits long prompts, once DIRECT
    (decode replicas pay the long prefills at their step boundaries)
    and once through the PREFILL POOL (a specialist prefills, KV hands
    off, decode admission takes the warm hit). The short stream's p99
    TTFT ratio (direct/pool) is the insulation number.
    """
    from bigdl_tpu import observability as obs
    from bigdl_tpu.serving import (DecodeScheduler, DisaggregatedFleet,
                                   FleetMonitor, RemoteReplica, Router,
                                   wait_for_members)
    import pickle
    import tempfile
    obs.enable()  # the handoff-latency histogram records in THIS process
    model_cfg = dict(vocab_size=128, hidden_size=64, num_heads=4,
                     filter_size=128, num_layers=2, max_len=512)
    sched_cfg = dict(max_slots=max_slots, block_size=16,
                     max_seq_len=384, prefill_chunk=16)
    model = _build_lm_model()
    plan = _lm_workload(n_clients, n_requests, 512)

    # -- arm A: single-process 2-replica router
    local = [DecodeScheduler(model, name=f"L{i}", **sched_cfg)
             for i in range(2)]
    rA = Router(local, name="local").start()
    thr_local, out_local, _ = _drive_fleet(
        lambda p, mn: rA.submit(p, max_new_tokens=mn), plan, rA.drain)
    rA.shutdown()

    # -- arm B: the same router logic over a 2-process fleet
    fd = tempfile.mkdtemp(prefix="bench_fleet_")
    params_path = os.path.join(fd, "params.pkl")
    import jax
    with open(params_path, "wb") as f:
        pickle.dump(jax.tree_util.tree_map(np.asarray, model.params), f)
    procs = [
        _spawn_fleet_agent(fd, "f0", "replica", 1, params_path,
                           model_cfg, sched_cfg),
        _spawn_fleet_agent(fd, "f1", "replica", 2, params_path,
                           model_cfg, sched_cfg),
        _spawn_fleet_agent(fd, "fp", "prefill", 3, params_path,
                           model_cfg, sched_cfg),
    ]
    docs = wait_for_members(fd, ["f0", "f1", "fp"], timeout_s=600)
    by = {d["name"]: d for d in docs}
    reps = [RemoteReplica(by["f0"], fleet_dir=fd),
            RemoteReplica(by["f1"], fleet_dir=fd)]
    rpf = RemoteReplica(by["fp"], fleet_dir=fd).start()
    rB = Router(reps, name="fleet", max_failovers=4).start()
    mon = FleetMonitor(reps + [rpf], fleet_dir=fd, every_s=0.25,
                       stale_s=15.0).start()
    thr_fleet, out_fleet, _ = _drive_fleet(
        lambda p, mn: rB.submit(p, max_new_tokens=mn), plan, rB.drain)
    match = (len(out_local) == len(out_fleet)
             and all(np.array_equal(out_local[k], out_fleet[k])
                     for k in out_local))

    # -- arm C: decode-p99 insulation from long-prompt prefill bursts
    rng = np.random.RandomState(7)
    nshort = max(2, n_clients - 1)
    short_plan = [[(rng.randint(1, 128, size=int(rng.randint(4, 13))
                                ).astype(np.int32), 8)
                   for _ in range(n_requests)] for _ in range(nshort)]
    # DISTINCT long prompts per arm: the direct arm's prefills register
    # in the decode replicas' prefix caches, so re-using one list would
    # hand the pool arm warm hits it never earned — the insulation
    # ratio must measure the handoff, not cache warmth from arm 1
    def _mk_longs():
        return [rng.randint(1, 128, size=int(rng.randint(160, 241))
                            ).astype(np.int32) for _ in range(n_long)]

    dis = DisaggregatedFleet(rB, [rpf], reps)

    def burst_and_drive(long_submit, longs):
        import threading as _t
        stop = _t.Event()

        def burster():
            i = 0
            while not stop.is_set() and i < len(longs):
                try:
                    long_submit(longs[i]).result(timeout=600)
                except Exception:
                    pass
                i += 1

        bt = _t.Thread(target=burster, daemon=True)
        bt.start()
        _, _, ttfts = _drive_fleet(
            lambda p, mn: rB.submit(p, max_new_tokens=mn), short_plan)
        stop.set()
        bt.join(timeout=600)
        return ttfts

    ttft_direct = burst_and_drive(
        lambda p: rB.submit(p, max_new_tokens=8), _mk_longs())
    ttft_pool = burst_and_drive(
        lambda p: dis.submit(p, max_new_tokens=8), _mk_longs())
    dst = dis.stats()

    # clean teardown: fleet drains, agents exit 0
    rpf.shutdown()
    rB.shutdown()
    mon.stop()
    codes = []
    for p in procs:
        try:
            codes.append(p.wait(timeout=180))
        except Exception:  # noqa: BLE001
            p.kill()
            codes.append(None)

    p99_direct = _pct(ttft_direct, 0.99)
    p99_pool = _pct(ttft_pool, 0.99)
    total = n_clients * n_requests
    lines = [{
        "metric": "serving_fleet_tokens_per_s",
        "value": round(thr_fleet, 1), "unit": "tok/s",
        "clients": n_clients, "requests": total,
        "processes": 2, "backend": _FLEET_AGENT_PLATFORM,
    }, {
        "metric": "serving_fleet_local_tokens_per_s",
        "value": round(thr_local, 1), "unit": "tok/s",
        "clients": n_clients, "requests": total, "backend": _FLEET_AGENT_PLATFORM,
    }, {
        "metric": "serving_fleet_vs_local",
        "value": round(thr_fleet / max(thr_local, 1e-9), 3), "unit": "x",
        "backend": _FLEET_AGENT_PLATFORM,
        "note": "cross-process fleet vs in-process 2-replica router at "
                "the same offered load (CPU box: transport+IPC tax)",
    }, {
        # process transparency is a CORRECTNESS claim: every fleet
        # response bitwise the in-process router's (1.0 or fail)
        "metric": "serving_fleet_token_match",
        "value": 1.0 if match else 0.0, "unit": "frac",
        "requests": total, "backend": _FLEET_AGENT_PLATFORM,
    }, {
        "metric": "serving_fleet_disagg_short_ttft_p99_ms",
        "value": round(p99_pool, 2), "unit": "ms",
        "handoffs": dst["handoffs"], "backend": _FLEET_AGENT_PLATFORM,
    }, {
        "metric": "serving_fleet_disagg_direct_short_ttft_p99_ms",
        "value": round(p99_direct, 2), "unit": "ms", "backend": _FLEET_AGENT_PLATFORM,
    }, {
        "metric": "serving_fleet_disagg_ttft_insulation",
        "value": round(p99_direct / max(p99_pool, 1e-9), 2), "unit": "x",
        "handoffs": dst["handoffs"], "long_prompts": n_long,
        "backend": _FLEET_AGENT_PLATFORM,
        "note": "short-stream p99 TTFT, long bursts direct vs through "
                "the prefill pool (>1 = the pool insulated decode)",
    }]
    # the per-hop handoff wall-time histogram (serve/fleet_handoff_ms)
    # rides the insulation line: the observability satellite's bench
    # surfacing — cluster_report.py shows the same number fleet-wide
    hh = obs.registry().get("serve/fleet_handoff_ms")
    if hh is not None and hh.count:
        lines[-1]["handoff_ms_mean"] = round(hh.mean, 2)
        lines[-1]["handoff_ms_max"] = round(hh.max, 2)
    return lines, dst, codes


def bench_serving_fleet_elastic(n_clients, n_requests, max_slots,
                                smoke=False):
    """ISSUE 19: the elastic arms.

    Arm D — scale-out goodput: a closed-loop shared-prefix load runs
    once against the 1-replica seed fleet (the pre-scale baseline),
    then the ``FleetController`` is attached and a sustained wave lets
    it grow the fleet to its budget (subprocess spawns, prefix-warmed
    joins, router join under live traffic — zero lost), and the SAME
    offered load is measured again at full size. The after/before
    tokens/s ratio is the scale-out goodput; on a contended CPU box it
    mostly measures how many real cores the box donates, so the band
    is wide.
    Arm E — scale-up-with-warming TTFT: two fresh replicas are spawned
    side by side, both compile-warmed with a prefix-free throwaway;
    one is prefix-warmed from a serving peer (``warm_replica``), the
    other joins cold. Median TTFT of shared-prefix probes, cold/warm,
    is the ratio — >1 means a warmed joiner answers its first real
    traffic without re-paying the shared prefill.
    """
    from bigdl_tpu import observability as obs
    from bigdl_tpu.serving import (FleetController, FleetMonitor,
                                   RemoteReplica, Router, ScalePolicy,
                                   wait_for_members, warm_replica)
    import pickle
    import tempfile
    obs.enable()
    model_cfg = dict(vocab_size=128, hidden_size=64, num_heads=4,
                     filter_size=128, num_layers=2, max_len=512)
    sched_cfg = dict(max_slots=max_slots, block_size=16,
                     max_seq_len=384, prefill_chunk=16)
    model = _build_lm_model()
    fd = tempfile.mkdtemp(prefix="bench_elastic_")
    params_path = os.path.join(fd, "params.pkl")
    import jax
    with open(params_path, "wb") as f:
        pickle.dump(jax.tree_util.tree_map(np.asarray, model.params), f)

    # every request shares a 96-token (block-aligned) system prefix:
    # the thing prefix warming actually moves to a joiner
    rng = np.random.RandomState(3)
    prefix = rng.randint(1, 128, size=96).astype(np.int32)

    def mk_plan(seed, nreq):
        r = np.random.RandomState(seed)
        return [[(np.concatenate([prefix, r.randint(
            1, 128, size=int(r.randint(4, 13))).astype(np.int32)]), 12)
            for _ in range(nreq)] for _ in range(n_clients)]

    procs = []

    def spawn(name):
        procs.append(_spawn_fleet_agent(fd, name, "replica",
                                        len(procs) + 1, params_path,
                                        model_cfg, sched_cfg))
        doc, = wait_for_members(fd, [name], timeout_s=600)
        return RemoteReplica(doc, fleet_dir=fd).start()

    e0 = spawn("e0")
    router = Router([e0], name="elastic", max_failovers=4).start()
    mon = FleetMonitor([e0], fleet_dir=fd, every_s=0.25,
                       stale_s=15.0).start()
    # growth 1->2 is the measured arm at every scale: a third competing
    # agent process on a core-limited box only starves the measurement
    # (deeper 1->3 growth is drilled in fleet_smoke / test_controller)
    max_size = 2
    pol = ScalePolicy(min_replicas=1, max_replicas=max_size,
                      queue_high=1.0, queue_low=0.0, up_ticks=1,
                      down_ticks=10**9, cooldown_s=0.5)
    ctl = FleetController(router, mon, fleet_dir=fd, spawn=spawn,
                          policy=pol, warm_prompts=lambda: [prefix],
                          every_s=0.5)

    # -- arm D: before / grow / after --------------------------------
    thr_before, _, _ = _drive_fleet(
        lambda p, mn: router.submit(p, max_new_tokens=mn),
        mk_plan(11, n_requests), router.drain)
    # a deep pre-burst of LONG generations pins an unambiguous backlog
    # in the member file before the first controller tick: short
    # 12-token requests drain faster than the 0.2s beat + 0.5s tick can
    # sample them, so the over-threshold score would be a race
    wave_rng = np.random.RandomState(29)
    wave_futs = [router.submit(np.concatenate([prefix, wave_rng.randint(
        1, 128, size=int(wave_rng.randint(4, 13))).astype(np.int32)]),
        max_new_tokens=48) for _ in range(64)]
    ctl.start()
    # sustained wave: an open-loop top-up keeps a real backlog on the
    # replicas (a closed loop of n_clients requests sits inside
    # max_slots and scores zero queue) so traffic stays live while
    # the subprocess spawn pays its jax-import tax
    grow_deadline = time.time() + 240
    while len(router.stats()["replicas"]) < max_size \
            and time.time() < grow_deadline:
        if sum(router.stats()["queue_depth"].values()) < 8 \
                and len(wave_futs) < 600:
            for _ in range(8):
                p = np.concatenate([prefix, wave_rng.randint(
                    1, 128, size=int(wave_rng.randint(4, 13))
                ).astype(np.int32)])
                wave_futs.append(router.submit(p, max_new_tokens=12))
        time.sleep(0.1)
    for f in wave_futs:
        f.result(timeout=600)
    scaled = len(router.stats()["replicas"])
    thr_after, _, _ = _drive_fleet(
        lambda p, mn: router.submit(p, max_new_tokens=mn),
        mk_plan(12, n_requests), router.drain)
    ctl.stop()
    cs = ctl.stats()
    rs = router.stats()
    lost = rs["submitted"] - rs["completed"] - rs["rejected"] - rs["doomed"]

    # -- arm E: warmed vs cold first-traffic TTFT ---------------------
    # ONLY the first shared-prefix request per joiner is a fair sample:
    # that very request inserts the prefix into the joiner's own cache,
    # so any later probe is a warm hit on BOTH sides (a median over 3
    # sequential probes compares warm-vs-warm and measures noise)
    def first_ttft(rep, seed):
        r = np.random.RandomState(seed)
        p = np.concatenate([prefix, r.randint(
            1, 128, size=9).astype(np.int32)])
        fut = rep.submit(p, max_new_tokens=4)
        fut.result(timeout=600)
        tr = fut.trace or {}
        return float(tr.get("ttft_ms") or 0.0)

    cold = spawn("cold0")
    warm = spawn("warm0")
    # compile-warm BOTH with prefix-free throwaways so arm E measures
    # the prefill skipped by warming, not first-dispatch XLA compiles
    for rep in (cold, warm):
        rep.submit(rng.randint(1, 128, size=104).astype(np.int32),
                   max_new_tokens=4).result(timeout=600)
    wout = warm_replica(e0, warm, [prefix])
    med_cold = first_ttft(cold, 41)
    med_warm = first_ttft(warm, 43)

    for rep in (cold, warm):
        rep.shutdown()
    router.shutdown()
    mon.stop()
    codes = []
    for p in procs:
        try:
            codes.append(p.wait(timeout=180))
        except Exception:  # noqa: BLE001
            p.kill()
            codes.append(None)

    sh = obs.registry().get("serve/fleet_spawn_ms")
    lines = [{
        "metric": "serving_fleet_elastic_scaleout_goodput",
        "value": round(thr_after / max(thr_before, 1e-9), 3), "unit": "x",
        "replicas_before": 1, "replicas_after": scaled,
        "tokens_per_s_before": round(thr_before, 1),
        "tokens_per_s_after": round(thr_after, 1),
        "scale_ups": cs["scale_ups"], "lost": lost, "backend": _FLEET_AGENT_PLATFORM,
        "spawn_ms_mean": round(sh.mean, 1) if sh is not None and sh.count
        else None,
        "spawn_count": sh.count if sh is not None else 0,
        "note": "closed-loop tokens/s after the controller grew the "
                "fleet vs the 1-replica seed (CPU box: bounded by real "
                "cores donated to the agent processes)",
    }, {
        "metric": "serving_fleet_warm_spawn_ttft_ratio",
        "value": round(med_cold / max(med_warm, 1e-9), 2), "unit": "x",
        "ttft_cold_ms": round(med_cold, 2),
        "ttft_warm_ms": round(med_warm, 2),
        "warmed_prompts": wout["warmed"], "warmed_tokens": wout["tokens"],
        "prefix_tokens": int(prefix.size), "backend": _FLEET_AGENT_PLATFORM,
        "note": "first-traffic TTFT on a cold joiner vs a prefix-warmed "
                "joiner, both compile-warmed; single first request per "
                "joiner — later requests hit the joiner's own prefix "
                "cache either way (>1 = the warmed replica skipped the "
                "shared prefill)",
    }]
    return lines, cs, lost, codes, wout


def main_fleet(smoke: bool):
    n_clients = int(os.environ.get("SERVE_FLEET_CLIENTS",
                                   2 if smoke else 4))
    n_requests = int(os.environ.get("SERVE_FLEET_REQUESTS",
                                    2 if smoke else 4))
    max_slots = int(os.environ.get("SERVE_FLEET_SLOTS", 4))
    n_long = int(os.environ.get("SERVE_FLEET_LONGS", 2 if smoke else 6))
    lines, dst, codes = bench_serving_fleet(n_clients, n_requests,
                                            max_slots, n_long,
                                            smoke=smoke)
    elines, ecs, elost, ecodes, ewout = bench_serving_fleet_elastic(
        n_clients, n_requests, max_slots, smoke=smoke)
    lines = lines + elines
    for line in lines:
        print(json.dumps(line), flush=True)
    _merge_metrics_dump(lines)
    by_metric = {l["metric"]: l for l in lines}
    failures = []
    # gates that hold at EVERY scale, smoke included
    if by_metric["serving_fleet_token_match"]["value"] != 1.0:
        failures.append("fleet responses diverged from the in-process "
                        "router (serving_fleet_token_match < 1.0)")
    if dst["handoffs"] < 1:
        failures.append("the pool sub-arm never handed off a prefix")
    if dst["handoff_failed"]:
        failures.append(f"{dst['handoff_failed']} handoffs failed on a "
                        "healthy fleet")
    if any(c != 0 for c in codes) or any(c != 0 for c in ecodes):
        failures.append(f"agent exit codes {codes}+{ecodes} "
                        "(expected clean 0s)")
    if elost:
        failures.append(f"{elost} requests lost across the elastic "
                        "scale-out (want 0)")
    if ewout["warmed"] < 1:
        failures.append("warm_replica moved no prefixes to the joiner")
    if not smoke:
        # ISSUE 19 acceptance on a measured run (the smoke run is a
        # plumbing check on whatever loaded CI box runs it)
        if ecs["scale_ups"] < 1:
            failures.append("the controller never scaled the fleet up "
                            "under the sustained wave")
        if by_metric["serving_fleet_warm_spawn_ttft_ratio"]["value"] \
                < 1.0:
            failures.append("prefix warming did not beat the cold "
                            "joiner's first-traffic TTFT")
    if failures:
        print("bench_serving --fleet: FAIL — " + "; ".join(failures),
              file=sys.stderr)
        raise SystemExit(1)
    egp = by_metric["serving_fleet_elastic_scaleout_goodput"]
    ewr = by_metric["serving_fleet_warm_spawn_ttft_ratio"]
    print(f"bench_serving --fleet: ok — fleet "
          f"{by_metric['serving_fleet_tokens_per_s']['value']} tok/s vs "
          f"local {by_metric['serving_fleet_local_tokens_per_s']['value']}"
          f" tok/s ({by_metric['serving_fleet_vs_local']['value']}x), "
          f"tokens bitwise == in-process; disagg short p99 TTFT "
          f"{by_metric['serving_fleet_disagg_short_ttft_p99_ms']['value']}"
          f"ms pooled vs "
          f"{by_metric['serving_fleet_disagg_direct_short_ttft_p99_ms']['value']}"
          f"ms direct (insulation "
          f"{by_metric['serving_fleet_disagg_ttft_insulation']['value']}x,"
          f" {dst['handoffs']} handoffs, handoff_ms mean "
          f"{by_metric['serving_fleet_disagg_ttft_insulation'].get('handoff_ms_mean', '-')}); "
          f"elastic 1->{egp['replicas_after']} goodput {egp['value']}x "
          f"(spawn_ms mean {egp.get('spawn_ms_mean', '-')}, "
          f"{egp['scale_ups']} ups, {elost} lost), warm-join TTFT "
          f"{ewr['ttft_warm_ms']}ms vs cold {ewr['ttft_cold_ms']}ms "
          f"({ewr['value']}x)")


def _run_router_arm(model, submit, tight_rps, bulk_rps, duration_s,
                    tight_ms, bulk_ms, n_gen=4):
    """One OPEN-LOOP mixed-class run: fixed-rate generators offer
    ``tight_rps`` + ``bulk_rps`` requests/s for ``duration_s``
    regardless of how the server keeps up — the load a population of
    independent users actually presents ("the same offered load" to
    every arm). ``submit(x, klass, deadline_ms)`` abstracts over the
    single engine (klass ignored) and the router.

    Outcomes are recorded via done-callbacks (latency = submit →
    outcome, misses included — an all-miss class must report its true
    tail, not an empty histogram); admission rejections (QueueFull /
    fail-fast doomed) count as misses at ~0 latency. GOODPUT counts
    only completions inside their own deadline. Returns (latency lists
    per class, miss counts per class, goodput req/s, wall seconds)."""
    from bigdl_tpu.serving import DeadlineExceeded, QueueFull
    rng = np.random.RandomState(0)
    samples = rng.randn(16, 784).astype(np.float32)
    lats = {"tight": [], "bulk": []}
    misses = {"tight": 0, "bulk": 0}
    good = [0]
    lock = threading.Lock()
    futures = []

    def on_done(fut, klass, deadline, t0):
        ms = (time.perf_counter() - t0) * 1000.0
        ok = fut.exception() is None
        with lock:
            lats[klass].append(ms)
            if ok and ms <= deadline:
                good[0] += 1
            else:
                misses[klass] += 1

    attempts = {"tight": 0, "bulk": 0}

    def generator(i):
        klass = "tight" if i < n_gen else "bulk"
        rate = (tight_rps if klass == "tight" else bulk_rps) / n_gen
        deadline = tight_ms if klass == "tight" else bulk_ms
        period = 1.0 / rate
        t_end = time.perf_counter() + duration_s
        t_next = time.perf_counter()
        k = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if now < t_next:
                time.sleep(t_next - now)
            t_next += period
            t0 = time.perf_counter()
            with lock:
                attempts[klass] += 1
            try:
                fut = submit(samples[k % 16], klass, deadline)
            except (DeadlineExceeded, QueueFull):
                with lock:   # shed at admission — a miss in ~µs
                    lats[klass].append(0.0)
                    misses[klass] += 1
                continue
            finally:
                k += 1
            fut.add_done_callback(
                lambda f, kl=klass, d=deadline, t=t0: on_done(f, kl, d, t))
            with lock:
                futures.append(fut)

    # cyclic-GC pauses are tens of ms on this box — a visible fraction
    # of a tight SLO. Refcounting still frees the per-request garbage;
    # the cycle collector just runs after the timed window instead of
    # in the middle of it (standard latency-bench hygiene).
    import gc
    gc.collect()
    gc.disable()
    try:
        dt = _client_pool(2 * n_gen, generator)
        # drain: every admitted request resolves (deadline expiry inside
        # the engines bounds this — nothing waits forever)
        for fut in futures:
            try:
                fut.exception(timeout=bulk_ms / 1000.0 + 60.0)
            except Exception:
                pass
    finally:
        gc.enable()
        gc.collect()
    lost = sum(attempts.values()) - len(lats["tight"]) - len(lats["bulk"])
    return lats, misses, good[0] / dt, {"attempts": dict(attempts),
                                        "lost": lost, "wall_s": dt}


def _build_router_model():
    """A meatier forward than LeNet (per-batch ~8ms on the 1-core dev
    box): the SLO bench needs service times in the tens of ms so
    deadline tiers separate cleanly from scheduler jitter."""
    from bigdl_tpu.nn import Linear, ReLU, Sequential
    m = Sequential(Linear(784, 1024), ReLU(), Linear(1024, 1024), ReLU(),
                   Linear(1024, 10))
    m.ensure_initialized()
    return m


def bench_serving_router(tight_rps, bulk_rps, duration_s, tight_ms,
                         bulk_ms, n_replicas, max_batch, max_wait_ms):
    from bigdl_tpu import observability as obs
    from bigdl_tpu.serving import PriorityClass, Router, ServingEngine

    obs.enable()
    model = _build_router_model()

    # -- arm 1: single-queue baseline (ONE replica, FIFO, no classes).
    # Under overload the bounded queue pins at capacity, so FIFO wait
    # sits at max_queue/drain-rate — structurally past the tight tier —
    # and admission sheds both classes indiscriminately: the two
    # deadline-blind failure modes the router exists to prevent.
    single = ServingEngine(model, input_shape=(784,), max_batch=max_batch,
                           max_wait_ms=max_wait_ms, max_queue=512,
                           name="single")
    with single:
        lat_s, miss_s, goodput_s, acct_s = _run_router_arm(
            model, lambda x, k, d: single.submit(x, deadline_ms=d),
            tight_rps, bulk_rps, duration_s, tight_ms, bulk_ms)
        st_s = single.stats()

    # -- arm 2: router over N replicas with weighted-fair classes ------
    # replica queues stay SHALLOW (max_batch) so backpressure lands in
    # the router, where class weights and deadlines can act on it
    replicas = [ServingEngine(model, input_shape=(784,),
                              max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              max_queue=max_batch, name=f"r{i}")
                for i in range(n_replicas)]
    # bulk depth_limit=2: keep replicas pipelined on bulk without
    # letting the bulk backlog stuff the replica FIFOs ahead of tight
    # arrivals — the head-of-line control that bounds tight latency
    router = Router(replicas, classes=[
        PriorityClass("tight", weight=8, max_queue=2048),
        PriorityClass("bulk", weight=1, max_queue=4096, depth_limit=2),
    ], fail_fast_factor=0.0)  # measure real misses, don't shed at admission
    with router:
        lat_r, miss_r, goodput_r, acct_r = _run_router_arm(
            model, lambda x, k, d: router.submit(x, klass=k, deadline_ms=d),
            tight_rps, bulk_rps, duration_s, tight_ms, bulk_ms)
        st_r = router.stats()

    tight_p99_s = _pct(lat_s["tight"], 0.99)
    tight_p99_r = _pct(lat_r["tight"], 0.99)
    lines = [{
        "metric": "serving_router_goodput_req_per_s",
        "value": round(goodput_r, 1), "unit": "req/s",
        "replicas": n_replicas, "tight_rps": tight_rps,
        "bulk_rps": bulk_rps, "duration_s": duration_s,
        "tight_deadline_ms": tight_ms,
        "bulk_deadline_ms": bulk_ms, "max_batch": max_batch,
        "tight_misses": miss_r["tight"], "bulk_misses": miss_r["bulk"],
        "failovers": st_r["failovers"], "lost": acct_r["lost"],
        "backend": _platform(),
    }, {
        "metric": "serving_single_goodput_req_per_s",
        "value": round(goodput_s, 1), "unit": "req/s",
        "tight_rps": tight_rps, "bulk_rps": bulk_rps,
        "tight_misses": miss_s["tight"], "bulk_misses": miss_s["bulk"],
        "lost": acct_s["lost"], "backend": _platform(),
    }, {
        "metric": "serving_router_goodput_ratio",
        "value": round(goodput_r / max(goodput_s, 1e-9), 2), "unit": "x",
        "replicas": n_replicas, "backend": _platform(),
    }, {
        "metric": "serving_router_tight_p99_ms",
        "value": round(tight_p99_r, 2), "unit": "ms",
        "tight_p50_ms": round(_pct(lat_r["tight"], 0.5), 2),
        "bulk_p99_ms": round(_pct(lat_r["bulk"], 0.99), 2),
        "backend": _platform(),
    }, {
        "metric": "serving_single_tight_p99_ms",
        "value": round(tight_p99_s, 2), "unit": "ms",
        "tight_p50_ms": round(_pct(lat_s["tight"], 0.5), 2),
        "bulk_p99_ms": round(_pct(lat_s["bulk"], 0.99), 2),
        "backend": _platform(),
    }, {
        "metric": "serving_router_tight_p99_ratio",
        "value": round(tight_p99_s / max(tight_p99_r, 1e-9), 2),
        "unit": "x", "backend": _platform(),
    }, {
        "metric": "serving_router_tight_misses",
        "value": miss_r["tight"], "unit": "requests",
        "offered": acct_r["attempts"]["tight"], "backend": _platform(),
    }, {
        # the gate-compatible form of "zero tight misses": the perf
        # gate skips zero-valued pins (a 0 reads as a failed capture),
        # so pin the in-deadline fraction at 1.0 with a tiny band
        "metric": "serving_router_tight_hit_rate",
        "value": round(1.0 - miss_r["tight"]
                       / max(acct_r["attempts"]["tight"], 1), 4),
        "unit": "frac", "backend": _platform(),
    }]
    return lines, st_s, st_r, miss_r, (acct_s, acct_r)


def main_router(smoke: bool):
    # The pinned load point is OPEN-LOOP OVERLOAD (1-core dev box,
    # ~8ms per-batch forward, one-queue capacity ~950 req/s): 700
    # tight + 500 bulk offered req/s exceed one queue's capacity, so
    # the single FIFO's wait pins at max_queue/drain (~400-700ms) and
    # the 250ms tight tier becomes unmeetable by a wide margin — while
    # the router serves the whole tight rate stably (p99 ~35ms quiet,
    # ~150ms under heavy box contention; the tier is sized for the
    # noisy case) and sheds only bulk. Deadline economics, not a
    # knife-edge: it holds wherever offered load > one queue's
    # capacity, which is the regime a router exists for.
    tight_rps = float(os.environ.get("SERVE_RT_TIGHT_RPS",
                                     60.0 if smoke else 700.0))
    bulk_rps = float(os.environ.get("SERVE_RT_BULK_RPS",
                                    40.0 if smoke else 500.0))
    duration_s = float(os.environ.get("SERVE_RT_SECONDS",
                                      1.5 if smoke else 10.0))
    tight_ms = float(os.environ.get("SERVE_RT_TIGHT_MS", 1000.0 if smoke
                                    else 250.0))
    bulk_ms = float(os.environ.get("SERVE_RT_BULK_MS", 30000.0))
    n_replicas = int(os.environ.get("SERVE_RT_REPLICAS", 2))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", 8))
    max_wait_ms = float(os.environ.get("SERVE_MAX_WAIT_MS", 2.0))
    lines, st_s, st_r, miss_r, (acct_s, acct_r) = bench_serving_router(
        tight_rps, bulk_rps, duration_s, tight_ms, bulk_ms, n_replicas,
        max_batch, max_wait_ms)
    for line in lines:
        print(json.dumps(line), flush=True)
    _merge_metrics_dump(lines)
    by_metric = {l["metric"]: l for l in lines}
    failures = []
    if acct_r["lost"] or acct_s["lost"]:
        failures.append(f"lost requests (no outcome): router "
                        f"{acct_r['lost']}, single {acct_s['lost']}")
    goodput_ratio = by_metric["serving_router_goodput_ratio"]["value"]
    p99_ratio = by_metric["serving_router_tight_p99_ratio"]["value"]
    if not smoke:
        # ISSUE 10 acceptance at the pinned load point (the smoke run is
        # a plumbing check on whatever loaded CI box runs it)
        if miss_r["tight"]:
            failures.append(f"{miss_r['tight']} tight-class deadline "
                            "misses through the router (want 0)")
        if goodput_ratio < 1.5:
            failures.append(f"router goodput {goodput_ratio}x single "
                            "replica < 1.5x acceptance")
        if p99_ratio < 1.0:
            failures.append(f"tight-class p99 ratio {p99_ratio}x < 1x "
                            "(single queue beat the router)")
    if failures:
        print("bench_serving --router: FAIL — " + "; ".join(failures),
              file=sys.stderr)
        raise SystemExit(1)
    print(f"bench_serving --router: ok — goodput "
          f"{by_metric['serving_router_goodput_req_per_s']['value']} req/s "
          f"over {n_replicas} replicas vs "
          f"{by_metric['serving_single_goodput_req_per_s']['value']} req/s "
          f"single queue ({goodput_ratio}x) at "
          f"{tight_rps + bulk_rps:.0f} offered req/s, tight p99 "
          f"{by_metric['serving_router_tight_p99_ms']['value']}ms vs "
          f"{by_metric['serving_single_tight_p99_ms']['value']}ms "
          f"({p99_ratio}x better), tight misses {miss_r['tight']} of "
          f"{acct_r['attempts']['tight']}")


def _merge_metrics_dump(lines):
    """Serving lines ride BENCH_METRICS.json next to the training bench
    lines: keep whatever bench.py last wrote, replace ONLY the stale
    entries this run re-measures (a --lm run must not delete the
    classic serving evidence, nor vice versa), append ours."""
    out = os.environ.get("BENCH_METRICS_OUT", "BENCH_METRICS.json")
    if not out:
        return
    if not os.path.isabs(out):
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)), out)
    from bigdl_tpu import observability as obs
    reg = obs.MetricsRegistry()
    for line in lines:
        obs.record_bench_line(line, reg)
    new = obs.metrics_dump(reg)
    stale = {str(e.get("metric", "")) for e in new}
    old = []
    try:
        with open(out) as f:
            old = [e for e in json.load(f)
                   if str(e.get("metric", "")) not in stale]
    except (OSError, ValueError):
        pass
    try:
        with open(out, "w") as f:
            json.dump(old + new, f, indent=1)
    except OSError as e:  # the dump must never fail the bench itself
        print(f"bench_serving: metrics dump failed: {e}", file=sys.stderr)


def main():
    smoke = "--smoke" in sys.argv
    if "--lm" in sys.argv:
        return main_lm(smoke)
    if "--router" in sys.argv:
        return main_router(smoke)
    if "--fleet" in sys.argv:
        return main_fleet(smoke)
    n_clients = int(os.environ.get("SERVE_CLIENTS", 4 if smoke else 16))
    n_requests = int(os.environ.get("SERVE_REQUESTS", 4 if smoke else 32))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", n_clients))
    max_wait_ms = float(os.environ.get("SERVE_MAX_WAIT_MS", 2.0))
    deadline_ms = float(os.environ.get("SERVE_DEADLINE_MS", 1000.0))
    lines, st, bad, dropped = bench_serving(
        n_clients, n_requests, max_batch, max_wait_ms, deadline_ms)
    for line in lines:
        print(json.dumps(line), flush=True)
    _merge_metrics_dump(lines)
    failures = []
    if bad:
        failures.append(f"{bad} client outputs mismatch the direct forward")
    if dropped:
        failures.append(f"{dropped} admitted requests never completed")
    if st["timeouts"]:
        failures.append(f"{st['timeouts']} requests timed out "
                        f"(deadline {deadline_ms}ms)")
    by_metric = {l["metric"]: l for l in lines}
    p99 = lines[0]["latency_p99_ms"]
    if p99 > deadline_ms:
        failures.append(f"p99 {p99}ms exceeds the {deadline_ms}ms deadline")
    if not any(lines[0][f"{s}_p99_ms"] > 0.0
               for s in ("queue_wait", "assemble", "dispatch")):
        failures.append("per-request stage decomposition missing "
                        "(serve/queue_wait|assemble|dispatch_ms empty)")
    speedup = by_metric["serving_batching_speedup"]["value"]
    if not smoke and speedup < 3.0:
        # the smoke run is a plumbing check on whatever loaded CI box runs
        # it; the throughput claim is only enforced on a measured run
        failures.append(f"batching speedup {speedup}x < 3x acceptance")
    if failures:
        print("bench_serving: FAIL — " + "; ".join(failures),
              file=sys.stderr)
        raise SystemExit(1)
    print(f"bench_serving: ok — {lines[0]['value']} req/s batched vs "
          f"{by_metric['serving_per_request_req_per_s']['value']} req/s "
          f"per-request predict() ({speedup}x), occupancy "
          f"{lines[0]['batch_occupancy_mean']}, p99 {p99}ms "
          f"(queue_wait {lines[0]['queue_wait_p99_ms']}ms / assemble "
          f"{lines[0]['assemble_p99_ms']}ms / dispatch "
          f"{lines[0]['dispatch_p99_ms']}ms)")


if __name__ == "__main__":
    main()
