"""Continuous batching: iteration-level LM decode scheduling.

The :class:`~.engine.ServingEngine` batches ONE-SHOT forwards — a
request enters a micro-batch, the batch dispatches, every row resolves.
Autoregressive decode breaks that shape: a request is not one forward
but hundreds, and whole-request batching (cut a batch, run EVERY
member's full generation, return together) makes short requests wait
for the longest member while freed rows decode as padding. Continuous
batching (Orca's iteration-level scheduling; the vLLM serving loop)
reschedules at DECODE-STEP boundaries instead: requests join the
running batch the step after they arrive, leave the step they finish,
and the ONE compiled decode step stays hot the whole time — scheduling
work onto fixed compiled shapes rather than reshaping per request, the
same discipline the training side's superstep/bucket work rides.

Shape discipline (why recompiles never happen mid-traffic):

* the KV cache is PAGED (``kv_cache.PagedKVCache``) — fixed-size blocks
  + per-request block tables, so heterogeneous sequence lengths share
  one pooled allocation and the compiled step's cache operand never
  changes shape;
* active rows pad to POWER-OF-TWO buckets (``optim.predictor.
  bucket_for`` — the serving engine's discipline) with a floor of 2:
  XLA CPU lowers 1-row matmuls to a gemv kernel that differs from the
  >=2-row gemm in the last ulp, and a bucket floor of 2 keeps every
  step of every request in ONE gemm M-class — that is what makes a
  request's tokens bitwise-identical whether it decodes alone or with
  the batch reshuffling around it (the correctness gate in
  tests/test_serving_lm.py);
* prompts prefill in fixed CHUNKS (pow-2-bucketed tail) through the
  same paged path, so a long prompt costs O(chunk * Tp) attention
  scratch and a bounded set of compiled shapes;
* shared prompt PREFIXES are served from the content-addressed prefix
  cache (``prefix_cache.PrefixCache`` over the refcounted block
  ledger): admission adopts the longest cached block-aligned prefix
  and skips its prefill chunks — the thousand-identical-system-prompts
  workload pays ONE prefill and stores the pages once, with
  copy-on-write forks guarding the shared pages (docs/SERVING.md
  "Prefix cache").

Hot swap: a request PINS the model version active at its admission and
keeps it to completion — swap() takes effect for later admissions, and
each dispatch serves exactly one version group, so no dispatch (and no
request continuation) ever mixes versions. Speculative decoding
(nn/speculative.py's draft-propose / chunk-verify pattern) rides the
same paged step BATCHED across the whole version group (ISSUE 14):
every greedy row drafts ``spec_k`` tokens in ``spec_k+1`` batched
paged draft steps, ONE chunked verify (``S = spec_k+1`` per row — a
shape ``decode_paged`` and the Pallas kernel already serve) scores
them all, and per-row acceptance lengths (``nn.speculative.
batched_acceptance``, computed in-program) advance each row
independently — rollback is the host-side per-row position counter
(rejected positions hold garbage the position-masked attention never
reads and the next round's writes overwrite; target and draft pools
stay in lockstep). Rows that cannot speculate — sampled rows, whose
acceptance rule is argmax-match — ride the SAME verify dispatch
masked to one real token, so a mixed batch still costs one program.

Per-request telemetry rides the PR-5 rid machinery: ``serve/prefill``
and ``serve/decode_step`` spans carry rids, and every future leaves
with a trace dict ({rid, queue_wait_ms, prefill_ms, ttft_ms, tpot_ms,
decode_steps, tokens, version}) plus the ``serve/ttft_ms`` /
``serve/tpot_ms`` histograms and the tokens/s lines the LM bench
(bench_serving.py --lm) reports. See docs/SERVING.md "Continuous
batching".
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as obs
from ..observability import cluster as _cluster
from ..observability import flight as _flight
from ..observability import health as _health
from ..optim.predictor import bucket_for
from ..parallel import chaos as _chaos
from ..parallel.failure import (FaultPolicy, TransientDeviceError,
                                classify_failure, TRANSIENT)
from ..utils import engine as _engine
from .batching import (DeadlineExceeded, EngineStopped, QueueFull,
                       ServeFuture)
from .kv_cache import (SPILL_PENDING, KVCacheOOM, KVSwapManager,
                       PagedKVCache, blocks_for_tokens)
from .prefix_cache import PrefixCache
from .registry import ModelRegistry

THREAD_NAME = "bigdl_tpu-serving-decode-scheduler"

_STAT_KEYS = ("submitted", "completed", "rejected", "timeouts",
              "decode_steps", "prefill_chunks", "tokens", "swaps",
              "spec_rounds", "spec_accepted", "spec_row_rounds",
              "spec_fallbacks", "defrags",
              "prefix_hits", "prefix_misses", "prefix_reused_tokens",
              "prefix_cow_forks", "step_replays", "kv_corruptions",
              "preemptions", "resumes", "resume_recomputes")


def _pow2_bucket(n: int, cap: int, floor: int = 2) -> int:
    """Smallest power of two >= n, floored (gemm M-class — see module
    docstring) and capped."""
    b = floor
    while b < n:
        b <<= 1
    return min(b, cap)


def prefill_schedule(prompt_len: int, chunk: int):
    """The chunked-prefill plan for a prompt: [(start, real, padded)].
    Full chunks run at ``chunk``; the tail pads to a power-of-two
    bucket (floor 2), so the compiled prefill shapes are bounded to
    {2, 4, ..., chunk}. Shared with the solo-decode oracle in
    tests/test_serving_lm.py so both sides chunk identically."""
    out = []
    s = 0
    while s < prompt_len:
        real = min(chunk, prompt_len - s)
        out.append((s, real, _pow2_bucket(real, chunk)))
        s += real
    return out


def prefill_padded_end(prompt_len: int, chunk: int) -> int:
    """Highest position (exclusive) the padded prefill writes — the
    capacity the block reservation must cover."""
    s, real, padded = prefill_schedule(prompt_len, chunk)[-1]
    return s + padded


class LMRequest:
    """One in-flight generation: prompt, budget, and decode state."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "future", "rid",
                 "deadline", "t_enqueue", "t_enqueue_ns", "t_admit_ns",
                 "t_first_ns", "t_done_ns", "prefill_ms", "version",
                 "model_version", "slot", "pos", "generated", "steps",
                 "chunks", "pf_i", "temperature", "top_p", "seed",
                 "hit_tokens", "adopted_n", "draft_pos", "spec_rounds",
                 "spec_accepted", "priority", "swap_handle", "resume_seq")

    def __init__(self, prompt, max_new_tokens, eos_id, deadline_s, rid,
                 temperature: float = 0.0, top_p: float = 1.0,
                 seed: int = 0, priority: int = 0):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0xFFFFFFFF
        self.future = ServeFuture()
        self.future.rid = rid
        self.rid = rid
        self.t_enqueue = time.monotonic()
        self.t_enqueue_ns = time.perf_counter_ns()
        self.t_admit_ns = None
        self.t_first_ns = None
        self.t_done_ns = None
        self.prefill_ms = 0.0
        self.deadline = (self.t_enqueue + deadline_s
                         if deadline_s is not None else None)
        self.version = None        # pinned at admission
        self.model_version = None  # the ModelVersion object (params ref)
        self.slot = None
        self.pos = 0               # next cache write position
        self.generated = []
        self.steps = 0             # decode dispatches this request rode
        self.chunks = None         # prefill_schedule, set at admission
        self.pf_i = 0              # next prefill chunk to run
        self.hit_tokens = 0        # prefix-cache hit length (tokens)
        self.adopted_n = 0         # shared blocks adopted at admission
        self.draft_pos = 0         # draft-cache write frontier (tokens);
        #                            < pos means the draft trails the
        #                            target and needs a catch-up prefill
        #                            before its next speculative round
        self.spec_rounds = 0       # speculative rounds this row rode
        self.spec_accepted = 0     # draft tokens the target accepted
        self.priority = int(priority)  # preemption class (higher wins)
        self.swap_handle = None    # HostKVHandle while preempted-to-host
        self.resume_seq = None     # host tokens to re-prefill when the
        #                            swap degraded to recompute

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now or time.monotonic()) > self.deadline)


class DecodeScheduler:
    """Iteration-level LM serving over one decoder-only model.

    Parameters
    ----------
    model : LM-mode ``nn.Transformer`` (``models.TransformerLM``).
    max_slots : fixed slot capacity of the running batch (>= 2); active
        rows pad to power-of-two buckets within it.
    block_size / max_seq_len : paged-KV geometry — ``max_seq_len``
        bounds prompt + generation per request (must be <= the model's
        ``max_len``); blocks hold ``block_size`` positions each.
    num_blocks : pooled block count (+1 reserved null block). Default
        sizes the pool so every slot can hold a full ``max_seq_len``
        sequence; shrink it to exercise admission backpressure.
    prefill_chunk : chunked-prefill piece size (pow-2, >= 2).
    draft_model : optional LM sharing the vocab — arms BATCHED greedy
        speculative decoding: at every step boundary, EVERY greedy row
        of a version group drafts ``spec_k`` tokens (batched paged
        draft steps) and one chunked verify dispatch scores the whole
        group, advancing each row by its own acceptance length
        (docs/SERVING.md "Speculative decoding (batched)"). Sampled
        rows ride the same verify masked to one real token;
        sampled-MAJORITY groups and boundaries with a prompt
        mid-prefill fall back to the plain step
        (``serve/spec_fallbacks``).
    admission : ``"continuous"`` (iteration-level — the point of this
        class) or ``"static"`` (whole-request batching: a batch admits
        only when the previous one fully drained — the bench baseline).
    eos_id : default end-of-sequence id (per-request override at
        ``submit``).
    prefix_cache : content-addressed KV block sharing
        (``prefix_cache.PrefixCache``, on by default). Admission looks
        up the longest cached block-aligned prefix of each prompt,
        ADOPTS those blocks (refcount +1, zero copies) and skips their
        prefill chunks entirely — on a hit, TTFT collapses to the tail
        chunk + the first decode step. Completed prefills register
        their full prompt blocks for future hits; under block pressure
        admission reclaims unreferenced entries LRU-first. Reuse is
        keyed on (tokens, model version), so a hot swap never crosses
        versions. Hits align to ``max(prefill_chunk, block_size)`` —
        the warm suffix then re-runs EXACTLY the cold schedule's
        remaining chunks (same shapes, same inputs), which is what
        keeps warm tokens bitwise-identical to a cold solo decode. A
        fully-cached aligned prompt re-runs only its LAST chunk for the
        first-token logits; that chunk's writes into shared pages take
        copy-on-write forks (reserved at admission — no mid-flight
        OOM).
    sampling_seed : base of the per-request sampling key stream
        (``engine.next_rng_keys``-style: one deterministic stream, one
        seed per request derived from it, so a request's samples are
        reproducible and independent of who shares its batch). Requests
        default to greedy; ``submit(..., temperature=, top_p=, seed=)``
        opts into sampling per request.
    mesh / placement : model-parallel serving — a
        ``jax.sharding.Mesh`` plus a param-placement policy (``"tp"`` /
        ``"fsdp"`` / spec tree / callable, see
        ``parallel.sharding.serving_param_specs``). Published versions
        device-put SHARDED onto the mesh, the paged KV pool lives on the
        mesh (kvH split over the ``model`` axis when it divides), and
        the same compiled paged step dispatches over it with
        XLA-inserted collectives. Speculative decoding is single-device
        only (``draft_model`` + ``mesh`` raises).
    name : replica name — per-replica watchdog beacon
        (``serving/decode_scheduler[<name>]``) for Router health
        integration.
    fault_policy : the Tier-2 retry budget for the compiled-step
        dispatch path (the serving analog of
        ``Optimizer.set_fault_policy``, one policy surface shared with
        :class:`~.engine.ServingEngine`'s batch retry). Every decode
        group / prefill chunk / speculative round snapshots the
        host-side step state (page handles + per-row counters) BEFORE
        dispatching; a failure classified TRANSIENT restores the
        snapshot, backs off, and replays the identical dispatch — the
        operands are immutable and the pages functional, so a replayed
        step is bitwise the step a fault-free run takes. PERMANENT
        failures (and an exhausted budget) kill the loop: a crash
        bundle with per-request triage lands, and every in-flight
        request fails typed :class:`EngineStopped` carrying its
        already-generated tokens on ``.partial`` — the splice point
        for the Router's KV-preserving failover. Default: one
        immediate retry (``FaultPolicy(max_restarts=1,
        backoff_base_s=0)``); pass ``FaultPolicy(max_restarts=0)`` to
        disable replay.
    audit_every : loop passes between KV-ledger audits
        (:meth:`audit`; 0 disables the cadence — shutdown still
        audits). A violation QUARANTINES the ledger instead of crashing
        the loop: a ``health/kv_corruption`` event + crash bundle land
        once, and admission stops creating NEW shared state (prefix
        lookups/registrations bypass) while in-flight traffic keeps
        draining.
    host_blocks : size of the host-RAM KV paging tier (ISSUE 18) in
        BLOCKS; 0 (default) disables it. When armed, prefix-cache
        evictions SPILL to host RAM instead of dropping (a later lookup
        refills — the second chance) and the scheduler gains swap-based
        preemption. All swaps are scheduled at step boundaries and
        staged asynchronously — the compiled step never blocks on a
        transfer (docs/SERVING.md "KV memory hierarchy").
    preempt : allow swap-based preemption (needs ``host_blocks``):
        when admission of a higher-``priority`` request hits block
        pressure, the lowest-priority decoding request's pages swap
        out, it re-enters the backlog, and re-admission refills and
        resumes BITWISE (the PR-13 snapshotted-handles argument; a
        failed stage degrades to recompute from host-resident tokens —
        never corrupt). ``False`` keeps spill/refill but never
        interrupts a running request.
    """

    def __init__(self, model, *, max_slots: int = 8, block_size: int = 16,
                 max_seq_len: int = 256, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 32, draft_model=None, spec_k: int = 4,
                 max_queue: int = 256,
                 default_deadline_ms: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 registry: Optional[ModelRegistry] = None,
                 admission: str = "continuous",
                 static_wait_ms: float = 4.0,
                 stall_deadline_s: Optional[float] = None,
                 sampling_seed: int = 0,
                 prefix_cache: bool = True,
                 prefix_cache_entries: Optional[int] = None,
                 mesh=None, placement=None,
                 name: Optional[str] = None,
                 tags=(),
                 fault_policy: Optional[FaultPolicy] = None,
                 audit_every: int = 256,
                 host_blocks: int = 0,
                 preempt: bool = True):
        if model.mode != "lm":
            raise ValueError("DecodeScheduler serves LM-mode models")
        if max_slots < 2:
            raise ValueError(f"max_slots must be >= 2 (the bucket floor "
                             f"— see module docstring), got {max_slots}")
        if prefill_chunk < 2 or (prefill_chunk & (prefill_chunk - 1)):
            raise ValueError(f"prefill_chunk must be a power of two >= 2, "
                             f"got {prefill_chunk}")
        if max_seq_len > model.max_len:
            raise ValueError(f"max_seq_len {max_seq_len} > model.max_len "
                             f"{model.max_len}")
        if admission not in ("continuous", "static"):
            raise ValueError(f"admission must be 'continuous' or 'static', "
                             f"got {admission!r}")
        if mesh is not None and draft_model is not None:
            raise ValueError("speculative decoding is single-device only — "
                             "drop draft_model or the mesh")
        model.ensure_initialized()
        self.model = model
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.prefill_chunk = int(prefill_chunk)
        self.admission = admission
        self.default_deadline_ms = default_deadline_ms
        self.eos_id = eos_id
        self.sampling_seed = int(sampling_seed)
        self.spec_k = int(spec_k)
        self.name = name
        # capability labels the Router's class→replica affinity matches
        # against PriorityClass(replica_tags=...) — e.g. an
        # int8-published replica tags itself "int8" so bulk traffic can
        # pin to it while tight traffic rides the f32 fleet
        self.tags = tuple(tags)
        self.beacon_name = ("serving/decode_scheduler" if name is None
                            else f"serving/decode_scheduler[{name}]")
        self.mesh = mesh
        self._page_axis = None   # mesh axis the pages' kvH dim splits over
        page_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel import sharding as _sh
            if registry is not None and placement is not None:
                raise ValueError(
                    "placement= is applied by the registry the scheduler "
                    "builds — with an explicit registry= it would be "
                    "silently ignored; construct the registry with "
                    "mesh/param_specs yourself, or drop one argument")
            self._op_sharding = NamedSharding(mesh, P())
            kvh = model.blocks[0].attn._kvh()
            if "model" in mesh.axis_names and mesh.shape["model"] > 1 \
                    and kvh % mesh.shape["model"] == 0:
                # pooled K/V pages split over KV heads: the decode-path
                # HBM lever under tensor parallelism — each shard holds
                # kvH/tp heads of every block
                page_sharding = NamedSharding(mesh, P(None, "model"))
                self._page_axis = "model"
            else:
                page_sharding = self._op_sharding
            if registry is None:
                registry = ModelRegistry(
                    mesh=mesh,
                    param_specs=_sh.serving_param_specs(
                        model.params, mesh, placement))
        mbs = blocks_for_tokens(max_seq_len, block_size)
        if num_blocks is None:
            num_blocks = self.max_slots * mbs + 1
        self.kv = PagedKVCache(model, num_blocks=num_blocks,
                               block_size=block_size,
                               max_blocks_per_seq=mbs,
                               sharding=page_sharding)
        # host-RAM paging tier (ISSUE 18): one async staging pipeline
        # under the device pool, shared by the prefix cache's second
        # chance and swap-based preemption
        self.kv_swap = (KVSwapManager(self.kv, host_blocks, tag=name)
                        if host_blocks > 0 else None)
        self.preempt_enabled = bool(preempt) and self.kv_swap is not None
        # prefix reuse aligns to max(chunk, block): hits leave the cold
        # schedule's remaining chunks intact (same compiled shapes, same
        # inputs — the bitwise contract; both are powers of two, so the
        # smaller always divides the larger)
        self.hit_align = max(self.prefill_chunk, int(block_size))
        self.prefix = (PrefixCache(self.kv,
                                   max_entries=prefix_cache_entries,
                                   swap=self.kv_swap)
                       if prefix_cache else None)
        self.draft_model = draft_model
        self.draft_kv = None
        if draft_model is not None:
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            draft_model.ensure_initialized()
            self.draft_kv = PagedKVCache(draft_model, num_blocks=num_blocks,
                                         block_size=block_size,
                                         max_blocks_per_seq=mbs,
                                         metric_prefix="serve/draft_kv")
        self.registry = registry or ModelRegistry()
        if self.registry.current() is None:
            self.registry.publish(model.params, model.state, version="v0",
                                  activate=True)
        self._greedy_args = {}  # bucket -> device-resident greedy triple
        # every program this scheduler compiles (warm-up's bucket x chunk
        # shapes above all) goes through the persistent cache, so a
        # restarted server warms from disk
        _engine.maybe_enable_compilation_cache()
        self._step_jit = self._build_step(model, "serve/decode_step")
        self._draft_jit = (self._build_step(draft_model, "serve/draft_step")
                           if draft_model is not None else None)
        # per-row acceptance lengths computed IN-PROGRAM: one readback
        # per spec round carries (accept_len, emitted tokens) for the
        # whole batch (nn/speculative.py)
        from ..nn.speculative import batched_acceptance
        self._accept_jit = jax.jit(batched_acceptance)
        self.static_wait_ms = float(static_wait_ms)
        self.max_queue = int(max_queue)
        self._q: queue.Queue = queue.Queue(maxsize=self.max_queue)
        self._defrag_wanted = threading.Event()
        self._backlog: deque = deque()   # scheduler-local, arrival order
        self._prefilling: deque = deque()  # admitted, prompt mid-prefill
        self._active: list = []          # decoding LMRequests, slot order
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._stop = threading.Event()
        self._pending = 0
        self._cond = threading.Condition()
        self._stats = dict.fromkeys(_STAT_KEYS, 0)
        self._stats_lock = threading.Lock()
        self._rids = itertools.count()
        self.stall_deadline_s = stall_deadline_s
        self._beacon = _health.NULL_BEACON
        self._snap_writer = _cluster.default_writer()
        # Tier-2 replay: default is the engine's historical one-shot
        # immediate retry, now expressed through the shared policy
        self.fault_policy = (fault_policy if fault_policy is not None
                             else FaultPolicy(max_restarts=1,
                                              backoff_base_s=0.0))
        self.audit_every = int(audit_every)
        self._audit_tick = 0
        self._quarantined = False

    def _build_step(self, model, name):
        """The ONE compiled paged decode step: next-token choices for
        every (row, chunk-position) plus the functionally-updated pages.
        Params are arguments, so every model version shares the
        executable; distinct (bucket, S) shapes compile once each.

        The trace runs under ``parallel.flash.paged_serving_context``
        carrying this scheduler's (mesh, kv-head shard axis), so the
        Pallas paged-attention kernel — when ``BIGDL_TPU_PAGED_ATTN``
        enables it — dispatches shard_map'd per kv-head group under TP
        placement and plain everywhere else. The draft model is
        single-device by construction (mesh+draft refused), so its step
        traces with no mesh.

        Token choice is per-row: greedy argmax when ``temps[b] <= 0``
        (bitwise the pre-sampling behavior — the correctness gate),
        temperature + top-p (nucleus) sampling otherwise. Sampling keys
        derive IN-PROGRAM from ``fold_in(PRNGKey(seeds[b]), position)``
        — a function of the request's seed and the absolute position
        only, so a sampled request draws the same tokens whether it
        decodes alone or mid-swarm (batch-mix independence, same
        contract the gemm M-class floor gives greedy). The whole
        sampling branch sits under ``lax.cond``: an all-greedy dispatch
        (the common case) never pays the sort."""

        def sample(logits, positions, seeds, temps, top_ps):
            B, S, V = logits.shape
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def sampled():
                base = jax.vmap(jax.random.PRNGKey)(
                    seeds.astype(jnp.uint32))
                pos = positions[:, None] + jnp.arange(S)[None, :]
                keys = jax.vmap(lambda k, ps: jax.vmap(
                    lambda p: jax.random.fold_in(k, p))(ps))(base, pos)
                t = jnp.maximum(temps, 1e-6)[:, None, None]
                scaled = logits / t
                order = jnp.argsort(-scaled, axis=-1)
                srt = jnp.take_along_axis(scaled, order, axis=-1)
                probs = jax.nn.softmax(srt, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # nucleus: keep while the mass BEFORE a token is < p
                # (the top-1 token always survives)
                keep = (cum - probs) < top_ps[:, None, None]
                masked = jnp.where(keep, srt, -jnp.inf)
                pick = jax.vmap(jax.random.categorical)(
                    keys.reshape(B * S, -1), masked.reshape(B * S, V))
                tok = jnp.take_along_axis(order.reshape(B * S, V),
                                          pick[:, None], axis=-1)[:, 0]
                tok = tok.reshape(B, S).astype(jnp.int32)
                # per-row: greedy rows of a mixed batch stay greedy
                return jnp.where(temps[:, None] > 0.0, tok, greedy)

            return jax.lax.cond(jnp.any(temps > 0.0), sampled,
                                lambda: greedy)

        mesh = self.mesh if model is self.model else None
        axis = self._page_axis if model is self.model else None
        from ..parallel import flash as _flash

        def step(params, pages, tokens, positions, tables, seeds, temps,
                 top_ps):
            with _flash.paged_serving_context(mesh=mesh, shard_axis=axis):
                logits, pages = model.decode_paged(
                    params, tokens, positions, pages, tables)
            return sample(logits, positions, seeds, temps, top_ps), pages

        return obs.perf.instrument_jit(jax.jit(step), name=name,
                                       kind="forward",
                                       key_argnums=(2, 3, 4))

    def _put(self, a):
        """Operand placement for one dispatch: replicated onto the mesh
        when serving model-parallel (params/pages carry the sharded
        placement; XLA inserts the collectives), plain transfer
        otherwise."""
        if self.mesh is not None:
            return jax.device_put(np.asarray(a), self._op_sharding)
        return jnp.asarray(a)

    def _sampling_args(self, rows, bucket):
        """(seeds, temps, top_ps) operands for one dispatch — padded
        slots are greedy (temp 0), so they never pay sampling work.
        The all-greedy triple (the default workload, and every padded
        warmup/draft/spec dispatch) is constant per bucket and cached
        device-resident, so the hot decode loop adds no per-step
        transfers until a request actually opts into sampling."""
        if all(r.temperature <= 0.0 for r in rows):
            cached = self._greedy_args.get(bucket)
            if cached is None:
                cached = (self._put(np.zeros((bucket,), np.uint32)),
                          self._put(np.zeros((bucket,), np.float32)),
                          self._put(np.ones((bucket,), np.float32)))
                self._greedy_args[bucket] = cached
            return cached
        seeds = np.zeros((bucket,), np.uint32)
        temps = np.zeros((bucket,), np.float32)
        top_ps = np.ones((bucket,), np.float32)
        for i, r in enumerate(rows):
            seeds[i] = r.seed
            temps[i] = r.temperature
            top_ps[i] = r.top_p
        return self._put(seeds), self._put(temps), self._put(top_ps)

    # -- lifecycle -------------------------------------------------------

    def start(self, warmup: bool = True):
        if self._thread is not None and self._thread.is_alive():
            return self
        if self._closed:
            raise EngineStopped("scheduler was shut down; build a new one")
        if warmup:
            self.warmup()
        self._beacon = _health.beacon(self.beacon_name,
                                      deadline_s=self.stall_deadline_s)
        self._thread = threading.Thread(target=self._run, name=THREAD_NAME,
                                        daemon=True)
        self._thread.start()
        return self

    def warmup(self):
        """Precompile EVERY shape the scheduler can dispatch — decode
        buckets {2, 4, ..., max_slots}, prefill chunk shapes
        {2, 4, ..., prefill_chunk}, and the speculative draft/verify
        shapes — by driving the compiled step against the null block
        table (writes land in the reserved garbage block). With the
        persistent compile cache on, a restarted server warms from disk;
        either way no live request ever pays an XLA compile."""
        def shapes_upto(cap, lo=2):
            out, b = [], lo
            while b < cap:
                out.append(b)
                b <<= 1
            out.append(cap)
            return out

        def drive(jit_fn, pages_of, B, S):
            cache = pages_of
            table = np.zeros((B, cache.max_blocks_per_seq), np.int32)
            with obs.span("serve/warmup_decode", shape=(B, S)):
                choices, pages = jit_fn(
                    self.registry.current().params if cache is self.kv
                    else self.draft_model.params,
                    cache.pages(), self._put(np.zeros((B, S), np.int32)),
                    self._put(np.zeros((B,), np.int32)), self._put(table),
                    *self._sampling_args((), B))
                cache.set_pages(pages)
                # sync-ok: warmup precompile — runs before serving starts
                jax.block_until_ready(choices)

        for b in shapes_upto(self.max_slots):
            drive(self._step_jit, self.kv, b, 1)
        for s in shapes_upto(self.prefill_chunk):
            drive(self._step_jit, self.kv, 1, s)
        if self.draft_model is not None:
            # batched speculation touches every (bucket, S) pair: the
            # draft steps and the S=spec_k+1 verify run at EVERY decode
            # bucket (the whole version group rides one round), and the
            # draft's (1, s) prefill/catch-up shapes mirror the
            # target's chunk schedule
            for b in shapes_upto(self.max_slots):
                drive(self._draft_jit, self.draft_kv, b, 1)
                drive(self._step_jit, self.kv, b, self.spec_k + 1)
                # the in-program acceptance schedule compiles per
                # bucket too — live traffic must add zero compiles
                # (operands ride _put like every live dispatch, so the
                # warmed placement matches; today mesh+draft is refused
                # and _put is a plain transfer, but the invariant must
                # survive a future mesh-served spec path)
                jax.block_until_ready(self._accept_jit(  # sync-ok: warmup
                    self._put(np.zeros((b, self.spec_k), np.int32)),
                    self._put(np.zeros((b, self.spec_k + 1), np.int32)),
                    self._put(np.zeros((b,), bool))))
            for s in shapes_upto(self.prefill_chunk):
                drive(self._draft_jit, self.draft_kv, 1, s)
        if self.kv_swap is not None:
            # the stager's bucketed gathers compile too — paying one on
            # the staging thread under live traffic stalls every spill
            # behind it (the second-chance window closes PENDING)
            self.kv_swap.warmup()
        return self

    def drain(self, timeout: Optional[float] = None) -> bool:
        with self._cond:
            return self._cond.wait_for(lambda: self._pending == 0, timeout)

    def shutdown(self, drain: bool = True, timeout: float = 60.0):
        """Graceful by default: stop admitting, serve everything already
        queued/active to completion, join. ``drain=False`` abandons all
        in-flight work with typed :class:`EngineStopped` failures. Either
        way every KV block returns to the free list before this returns
        (``serve/kv_blocks_in_use`` drains to zero — the leak gate)."""
        with self._cond:
            self._closed = True
        if not drain:
            self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                import logging
                # the drain is overrunning its budget: hard-stop the
                # loop and give it one short grace to exit at the next
                # step boundary — the cleanup below mutates scheduler-
                # owned state and MUST NOT race a live loop
                logging.getLogger(__name__).warning(
                    "decode scheduler did not join within %.0fs — "
                    "hard-stopping", timeout)
                self._stop.set()
                t.join(10.0)
                if t.is_alive():
                    # wedged inside a dispatch: leave its state alone
                    # (freeing live requests' blocks under a running
                    # loop would let a later admission alias their
                    # pages); the stall watchdog owns this failure mode
                    logging.getLogger(__name__).error(
                        "decode scheduler wedged — skipping state "
                        "cleanup; clients fail via the stall watchdog")
                    self._beacon.close()
                    return
        self._beacon.close()
        # hard stop (or a dead scheduler): fail whatever is left, free
        # its blocks — a client must never hang and a block never leak
        self._abandon_inflight("scheduler shut down before completion")
        # the shutdown audit: the ledger must be consistent at the end
        # of every run (violations quarantine + bundle, never raise)
        self._audit("shutdown")
        # every owner is gone — drop the prefix cache's pins so the
        # shared pages return too (the kv_blocks_in_use -> 0 leak gate
        # holds on every shutdown path, sharing included)
        if self.prefix is not None:
            self.prefix.clear()
        # ... and the host tier drains with it: _release settled every
        # preempted handle, prefix.clear() every spilled one, so the
        # stager has nothing live left — stop it (the wedged path above
        # returns early and leaves the daemon thread; the stall
        # watchdog owns that failure mode)
        if self.kv_swap is not None:
            self.kv_swap.shutdown()

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.shutdown(drain=exc_type is None)
        return False

    # -- client surface --------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: int,
               deadline_ms: Optional[float] = None,
               eos_id="default", temperature: float = 0.0,
               top_p: float = 1.0,
               seed: Optional[int] = None,
               priority: int = 0) -> ServeFuture:
        """Enqueue ONE generation request: ``prompt_ids`` (1-D int) →
        future resolving to the GENERATED ids (np.int32, prompt
        excluded). Raises :class:`QueueFull` / typed rejection
        on over-budget requests; a deadline that expires mid-generation
        fails the future with :class:`DeadlineExceeded` whose
        ``partial`` attribute carries the tokens generated so far.

        ``temperature=0`` (default) decodes greedy — bitwise the
        pre-sampling behavior. ``temperature>0`` samples with top-p
        ``top_p`` under a per-request key stream: ``seed`` pins the
        stream explicitly (same seed ⇒ same tokens, regardless of
        batch mix); when None, the seed derives deterministically from
        the scheduler's ``sampling_seed`` and this request's rid.

        ``priority`` is the preemption class (default 0): with the host
        tier armed, admission of a higher-priority request under block
        pressure may swap a lower-priority DECODING request out to host
        RAM; the victim resumes bitwise when blocks free up. Equal
        priorities never preempt each other."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must be non-empty")
        spec_over = (self.spec_k + 1) if self.draft_model is not None else 0
        worst = max(prefill_padded_end(prompt.size, self.prefill_chunk),
                    prompt.size + max_new_tokens + spec_over)
        if worst > self.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new_tokens} "
                f"(+ padding/speculation headroom) needs {worst} positions "
                f"> max_seq_len {self.max_seq_len}")
        ms = deadline_ms if deadline_ms is not None \
            else self.default_deadline_ms
        eid = self.eos_id if eos_id == "default" else eos_id
        rid = next(self._rids)
        if seed is None:
            # next_rng_keys-style stream: a splitmix-flavored fold of
            # (base, rid) — deterministic per request, decorrelated
            # across requests, zero device work
            seed = ((self.sampling_seed * 0x9E3779B9 + rid * 0x85EBCA6B
                     + 0xC2B2AE35) & 0xFFFFFFFF)
        req = LMRequest(prompt, max_new_tokens, eid,
                        ms / 1000.0 if ms is not None else None,
                        rid, temperature=temperature, top_p=top_p,
                        seed=seed, priority=priority)
        try:
            with self._cond:
                if self._closed:
                    raise EngineStopped("scheduler is shutting down")
                self._q.put_nowait(req)
                self._pending += 1
        except queue.Full:
            self._bump("rejected")
            if obs.enabled():
                obs.counter("serve/rejected").inc()
            raise QueueFull(
                f"request queue at capacity ({self.max_queue}) — shed or "
                "retry with backoff")
        req.future.add_done_callback(lambda f: self._on_done(f))
        self._bump("submitted")
        return req.future

    def generate(self, prompt_ids, max_new_tokens: int,
                 timeout: Optional[float] = None, **kw) -> np.ndarray:
        """Synchronous convenience: ``submit(...).result(timeout)``."""
        if self._thread is None:
            raise RuntimeError("scheduler not started — call start() or "
                               "use it as a context manager")
        return self.submit(prompt_ids, max_new_tokens, **kw).result(timeout)

    def swap(self, params, state=None, version: Optional[str] = None) -> str:
        """Hot swap: load + activate a new version. In-flight requests
        keep the version they pinned at admission to their last token
        (dispatches are cut per version group — no program ever sees two
        param sets); admissions after this call serve the new version.
        ``state=None`` inherits the active version's state (a
        params-only swap must not change the compiled step's pytree)."""
        if state is None:
            cur = self.registry.current()
            state = cur.state if cur is not None else self.model.state
        v = self.registry.publish(params, state, version=version,
                                  activate=False)
        self.registry.activate(v)
        self._bump("swaps")
        if obs.enabled():
            obs.instant("serve/swap", version=v)
        return v

    def defrag(self) -> int:
        """Request a block-pool defrag at the next step boundary (safe:
        the scheduler thread runs it between dispatches). Synchronous
        when called before start() or after shutdown."""
        if self._thread is None or not self._thread.is_alive():
            n = self.kv.defrag()
            if self.draft_kv is not None:
                n += self.draft_kv.defrag()
            if n:
                self._bump("defrags")
            return n
        self._defrag_wanted.set()
        return -1  # deferred; watch serve/kv_defrag_moves

    def stats(self) -> dict:
        with self._stats_lock:
            out = dict(self._stats)
        out["pending"] = self._pending
        out["queue_depth"] = self._q.qsize() + len(self._backlog)
        out["active"] = len(self._active)
        out["prefilling"] = len(self._prefilling)
        out["active_version"] = self.registry.active_version
        out["quarantined"] = self._quarantined
        out["kv"] = self.kv.stats()
        out["prefix"] = (self.prefix.stats() if self.prefix is not None
                         else None)
        out["host"] = (self.kv_swap.stats() if self.kv_swap is not None
                       else None)
        return out

    def cached_prefix_tokens(self, prompt_ids) -> int:
        """Router-affinity probe: how many leading tokens of this
        prompt admission would actually REUSE from this replica's
        prefix cache under the active version — the raw resident chain
        aligned down to ``hit_align``, so the router never steers a
        request toward a fragment admission will discard. Pure host
        work (a digest walk) — safe to call from router dispatch
        threads; 0 with the cache disabled (or the ledger
        quarantined — the router must not steer toward a cache
        admission will refuse to adopt from)."""
        if self.prefix is None or self._quarantined:
            return 0
        mv = self.registry.current()
        if mv is None:
            return 0
        t = self.prefix.peek(prompt_ids, mv.version)
        return t - t % self.hit_align

    # -- transient step replay (Tier-2, ISSUE 13) ------------------------

    def _snapshot_step_state(self, rows):
        """Host-side snapshot of everything ONE compiled step group can
        mutate, taken BEFORE the dispatch: the functional page handles
        of both pools (the compiled step returns NEW handles — holding
        the old ones IS the rollback) and the per-row decode counters.
        Pure reference/int copies — no device touch, no allocation
        proportional to model size."""
        return (self.kv.pages(),
                self.draft_kv.pages() if self.draft_kv is not None
                else None,
                [(r, r.pos, r.steps, len(r.generated), r.pf_i,
                  r.draft_pos) for r in rows])

    def _restore_step_state(self, snap):
        pages, dpages, rows = snap
        self.kv.set_pages(pages)
        if dpages is not None:
            self.draft_kv.set_pages(dpages)
        for r, pos, steps, ngen, pf_i, draft_pos in rows:
            r.pos, r.steps, r.pf_i = pos, steps, pf_i
            r.draft_pos = draft_pos
            del r.generated[ngen:]

    def _replay_group(self, stage, rows, fn):
        """Dispatch ``fn`` under the fault policy: a failure classified
        into the policy's retry classes restores the pre-dispatch
        snapshot, backs off (injectable sleep — fault drills run at
        full speed), and replays. The operand arrays are immutable and
        the snapshot restores the exact page handles, so a replayed
        group is BITWISE the group a fault-free run dispatches — the
        serving analog of the trainer's superstep replay. Failures
        outside the budget/classes propagate to :meth:`_die` (crash
        bundle + typed in-flight failures)."""
        pol = self.fault_policy
        snap = self._snapshot_step_state(rows)
        while True:
            try:
                out = fn()
            except BaseException as e:  # noqa: BLE001 — classify, maybe replay
                cls = classify_failure(e)
                self._restore_step_state(snap)
                if pol is None or self._stop.is_set() \
                        or not pol.should_retry(cls):
                    raise
                pol.record_failure()
                self._bump("step_replays")
                if obs.enabled():
                    obs.counter("serve/step_replays").inc()
                _health.emit("serve_step_replay", stage=stage,
                             failure_class=cls, attempt=pol.consecutive,
                             rids=[r.rid for r in rows],
                             error=f"{type(e).__name__}: {e}")
                delay = pol.backoff_s()
                if delay > 0:
                    pol.sleep(delay)
                continue
            if pol is not None:
                pol.record_success()
            return out

    # -- KV ledger auditor (ISSUE 13) ------------------------------------

    def audit(self) -> dict:
        """Run the ledger invariant checker over the target pool (with
        the prefix cache's exact pin map) and the draft pool. Pure host
        work at a quiesced point — the scheduler thread runs it on the
        ``audit_every`` cadence and at shutdown; callers may run it any
        time the loop is not mid-dispatch. Returns the merged
        :meth:`PagedKVCache.audit` report."""
        pins = (self.prefix.pinned_blocks() if self.prefix is not None
                else {})
        rep = self.kv.audit(prefix_pins=pins)
        if self.draft_kv is not None:
            drep = self.draft_kv.audit(prefix_pins={})
            rep = {"ok": rep["ok"] and drep["ok"],
                   "violations": rep["violations"]
                   + [f"draft: {v}" for v in drep["violations"]],
                   "blocks": rep["blocks"] + drep["blocks"],
                   "owners": rep["owners"] + drep["owners"]}
        return rep

    def _audit(self, where: str) -> dict:
        """Cadence/shutdown audit: a violation QUARANTINES instead of
        crashing — serving a corrupt ledger read-only beats killing
        every in-flight client, but creating NEW shared state in it
        (prefix adoption, registration) would spread the corruption, so
        that stops. One ``health/kv_corruption`` event + crash bundle
        land on the FIRST detection; later audits just count."""
        rep = self.audit()
        if rep["ok"]:
            return rep
        first = not self._quarantined
        self._quarantined = True
        if first:
            # one corruption episode = ONE count on both surfaces (the
            # stats key and the obs counter stay in lockstep, like
            # every other stat here); later cadence audits of the same
            # quarantined ledger change nothing
            self._bump("kv_corruptions")
            if obs.enabled():
                obs.counter("serve/kv_corruptions").inc()
            _health.emit("kv_corruption", component=self.beacon_name,
                         where=where, n_violations=len(rep["violations"]),
                         violations=rep["violations"][:8])
            if obs.enabled():
                _flight.dump_crash_bundle(error=None, context={
                    "component": "serving/decode_scheduler",
                    "event": "kv_corruption", "where": where,
                    "violations": rep["violations"][:32],
                    "requests": self._triage()})
        return rep

    # -- scheduler loop --------------------------------------------------

    def _run(self):
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — post-mortem, then die
            self._die(e)
            raise

    def _triage(self):
        """Per-request state for the crash bundle: who was in flight,
        how far along, and what it held — the table
        ``tools/flight_report.py`` renders as the post-mortem's
        in-flight section."""
        out = []

        def add(r, stage):
            out.append({"rid": r.rid, "stage": stage,
                        "prompt_len": int(r.prompt.size),
                        "tokens": len(r.generated),
                        "kv_blocks": self.kv.owned(r.rid),
                        "version": r.version})

        for r in self._active:
            add(r, "decode")
        for r in self._prefilling:
            add(r, "prefill")
        for r in self._backlog:
            add(r, "backlog")
        return out

    def _die(self, error):
        """Loop death (a PERMANENT dispatch fault, or an exhausted
        replay budget): land the crash bundle WITH per-request triage,
        then fail every in-flight request typed — active/prefilling
        requests carry the tokens they already generated on
        ``exc.partial``, which is what lets a Router failover re-seed a
        survivor with ``prompt + partial`` instead of losing the decode
        state — and return every block so the ledger drains."""
        if obs.enabled():
            _flight.dump_crash_bundle(error=error, context={
                "component": "serving/decode_scheduler",
                "failure_class": classify_failure(error),
                "requests": self._triage(),
                "stats": {k: v for k, v in self.stats().items()
                          if k not in ("kv", "prefix")}})
        with self._cond:
            self._closed = True
        self._abandon_inflight(
            f"decode scheduler died: {type(error).__name__}: {error}")
        if self.prefix is not None:
            self.prefix.clear()
        if self.kv_swap is not None:
            self.kv_swap.shutdown()
        self._beacon.close()

    def _abandon_inflight(self, msg: str):
        """Gather every request the scheduler still holds (active,
        prefilling, backlogged, queued), release their resources, and
        fail each typed :class:`EngineStopped` with the generated
        prefix attached on ``.partial`` — the Router's KV-preserving
        splice point. Both death paths (shutdown's hard-stop cleanup
        and :meth:`_die`) share this, so the partial-carrying contract
        cannot drift between them."""
        leftovers = list(self._active) + list(self._prefilling) \
            + list(self._backlog)
        self._active.clear()
        self._prefilling.clear()
        self._backlog.clear()
        while True:
            try:
                leftovers.append(self._q.get_nowait())
            except queue.Empty:
                break
        for r in leftovers:
            partial = np.asarray(r.generated, np.int32)
            self._release(r)
            if not r.future.done():
                exc = EngineStopped(msg)
                # these tokens are real — bitwise the uninterrupted
                # run's prefix — so a failover can resume from them
                exc.partial = partial
                try:
                    r.future.set_exception(exc)
                except Exception:
                    pass

    def _loop(self):
        """The iteration-level loop: every pass is one step boundary —
        drain arrivals, admit into free slots, advance ONE prefill
        chunk, ONE decode dispatch per active version group, evict
        finished/expired rows. Prefill is interleaved chunk-at-a-time
        so a joining long prompt never head-of-line-blocks the running
        batch for more than one chunk's forward. Nothing in here blocks
        on the device except the per-step token readbacks."""
        while not self._stop.is_set():
            self._beacon.pulse()
            if obs.enabled():
                self._snap_writer.maybe_write()
            self._drain_arrivals()
            self._admit()
            stepped = self._advance_prefill()
            stepped |= self._step_all()
            self._evict_expired()
            if self._defrag_wanted.is_set():
                self._defrag_wanted.clear()
                try:
                    n = self.kv.defrag()
                    if self.draft_kv is not None:
                        n += self.draft_kv.defrag()
                except Exception as e:  # noqa: BLE001 — transient = skip
                    # a TRANSIENT page-copy failure aborts the repack
                    # with the ledger untouched — skip the round (the
                    # next defrag() request retries) rather than kill
                    # every in-flight generation over an optimization
                    if classify_failure(e) != TRANSIENT:
                        raise
                    _health.emit("serve_defrag_skipped",
                                 error=f"{type(e).__name__}: {e}")
                else:
                    if n:
                        self._bump("defrags")
            if self.audit_every > 0:
                self._audit_tick += 1
                if self._audit_tick >= self.audit_every:
                    self._audit_tick = 0
                    self._audit("cadence")
            if self._closed and not self._active and not self._prefilling \
                    and not self._backlog and self._q.empty():
                break
            if not stepped:
                # idle (or static mode waiting out its fill window):
                # block briefly on the queue so arrival→admission
                # latency stays low without a spin
                try:
                    self._backlog.append(self._q.get(
                        timeout=0.002 if self._backlog else 0.02))
                    self._pull_pending()
                except queue.Empty:
                    pass

    def _pull_pending(self):
        while True:
            try:
                self._backlog.append(self._q.get_nowait())
            except queue.Empty:
                return

    def _drain_arrivals(self):
        self._pull_pending()

    def _admit(self):
        """Admit backlog head-of-line into free slots at this step
        boundary. A request is admitted only when its WORST-CASE block
        need is reservable, so no later step can OOM mid-flight; static
        mode additionally waits for the running batch to fully drain
        (whole-request batching — the bench baseline). FIFO order is
        kept even when a smaller later request would fit (no starvation
        of large requests)."""
        if self.admission == "static":
            if self._active or self._prefilling:
                return
            if self._backlog and len(self._backlog) < self.max_slots \
                    and not self._closed:
                # whole-request batching needs a fill window (the
                # ServingEngine's max_wait_ms analog): wait briefly for
                # the batch to fill rather than running a batch of one
                oldest = self._backlog[0].t_enqueue
                if (time.monotonic() - oldest) * 1000.0 < \
                        self.static_wait_ms:
                    return
        while self._backlog and self._free_slots:
            req = self._backlog[0]
            if req.future.cancelled():
                self._backlog.popleft()
                self._finish(req, cancel=True)
                continue
            if req.expired():
                self._backlog.popleft()
                self._expire(req)
                continue
            if req.swap_handle is not None or req.resume_seq is not None:
                # a preempted request resumes through refill-before-
                # resume, never ordinary admission (its decode state is
                # on the host tier, not in its prompt). Deferring keeps
                # it at the head — FIFO, so resumption cannot starve
                # behind a stream of fresh arrivals.
                if not self._resume_preempted(req):
                    break
                continue
            # spec_over is PER SLOT: under batched speculation every
            # active row (sampled ones included — they ride the verify
            # dispatch masked to one real token, whose padded lanes
            # still write k+1 positions) may overshoot by spec_k+1
            spec_over = (self.spec_k + 1) if self.draft_model is not None \
                else 0
            worst = max(
                prefill_padded_end(req.prompt.size, self.prefill_chunk),
                req.prompt.size + req.max_new_tokens + spec_over)
            mv = self.registry.current()
            cold = prefill_schedule(req.prompt.size, self.prefill_chunk)
            plan, adopted, fork_idxs = self._prefix_plan(req, mv.version,
                                                         cold)
            forked = []
            try:
                # worst-case PRIVATE need: total blocks minus the shared
                # prefix it adopts, plus the copy-on-write pages its
                # warm plan must fork
                need = (blocks_for_tokens(worst, self.kv.block_size)
                        - len(adopted) + len(fork_idxs))
                if adopted:
                    self.kv.adopt(req.rid, adopted)
                try:
                    if self.prefix is not None \
                            and not self.kv.can_allocate(need):
                        # block pressure: reclaim unreferenced prefix
                        # entries (LRU, leaf-first) before deferring —
                        # the blocks just adopted are pinned (refcount
                        # >= 2) and cannot be taken back out from under
                        # this request
                        self.prefix.evict(need - self.kv.blocks_free())
                    if not self.kv.can_allocate(need):
                        raise KVCacheOOM(
                            f"need {need} private blocks, "
                            f"{self.kv.blocks_free()} free")
                    self.kv.ensure_capacity(req.rid, worst)
                    if self.draft_kv is not None:
                        self.draft_kv.ensure_capacity(req.rid, worst)
                    if fork_idxs:
                        # copy-on-write EAGERLY, inside the same
                        # admission transaction that checked the free
                        # list: a later admission may consume every
                        # free block, and a fork deferred to prefill
                        # time would then OOM mid-flight (the invariant
                        # this whole block exists to uphold)
                        forked = self.kv.fork_blocks(req.rid, fork_idxs)
                except (KVCacheOOM, TransientDeviceError):
                    # undo the adoption and any partial growth — a
                    # deferred request must leave the ledger untouched
                    self.kv.free(req.rid)
                    raise
            except (KVCacheOOM, TransientDeviceError) as e:
                # backpressure: leave it queued — eviction will free
                # blocks and the next boundary retries. A TRANSIENT
                # fault in the admission transaction (an injected
                # cow-fork/evict failure) takes the same deferral:
                # the transaction unwound, the request just waits.
                # Under REAL block pressure a higher-priority arrival
                # may instead swap a lower-priority decoding request
                # out to the host tier and retry immediately (ISSUE
                # 18) — admission stops deferring when spilling a
                # victim frees enough blocks.
                if isinstance(e, KVCacheOOM) and self._try_preempt(req):
                    continue
                break
            self._backlog.popleft()
            req.slot = self._free_slots.pop()
            req.version = mv.version
            req.model_version = mv
            req.t_admit_ns = time.perf_counter_ns()
            req.chunks = plan
            req.pf_i = 0
            if self.prefix is not None:
                self._bump("prefix_hits" if req.hit_tokens
                           else "prefix_misses")
                # honest savings accounting: tokens the warm plan does
                # NOT prefill — in the rerun-last-chunk case the tail
                # chunk's tokens are re-computed, so they don't count
                reused = int(req.prompt.size) - sum(c[1] for c in plan)
                if reused:
                    self._bump("prefix_reused_tokens", reused)
                if forked:
                    self._bump("prefix_cow_forks", len(forked))
                if obs.enabled():
                    if req.hit_tokens:
                        obs.counter("serve/prefix_hits").inc()
                    else:
                        obs.counter("serve/prefix_misses").inc()
                    if reused:
                        obs.counter("serve/prefix_reused_tokens").inc(
                            reused)
                    if forked:
                        obs.counter("serve/prefix_cow_forks").inc(
                            len(forked))
            if not req.future.set_running_or_notify_cancel():
                self._finish(req, cancel=True)
                continue
            self._prefilling.append(req)

    def _prefix_plan(self, req, version, cold):
        """Prefill-skip admission: returns ``(chunks_to_run,
        adopted_blocks, cow_fork_idxs)``. A miss (or a disabled cache)
        runs the full cold schedule. A hit adopts the longest cached
        ``hit_align``-aligned prefix and keeps only the cold schedule's
        chunks at/after it — identical shapes over identical inputs, so
        warm tokens stay bitwise the cold solo decode's. A FULLY cached
        aligned prompt keeps just its last chunk (the first-token
        logits must still be computed); the adopted blocks that chunk
        overwrites are returned as ``cow_fork_idxs`` for the admission
        transaction to fork EAGERLY — deferring the fork to prefill
        time would let an interleaved admission drain the free list and
        OOM it mid-flight."""
        req.hit_tokens = 0
        req.adopted_n = 0
        if self.prefix is None or self._quarantined:
            # a quarantined ledger serves, but adopting shared pages
            # out of it would spread whatever the auditor caught
            return cold, [], []
        bs = self.kv.block_size
        chain = self.prefix.lookup(req.prompt, version)
        h = min(len(chain) * bs, int(req.prompt.size))
        h -= h % self.hit_align
        if h <= 0:
            return cold, [], []
        adopted = chain[:h // bs]
        plan = [c for c in cold if c[0] >= h] or [cold[-1]]
        fork_idxs = []
        s0, _, padded0 = plan[0]
        if s0 < h:
            # rerun-last-chunk case: adopted blocks the chunk overwrites
            fork_idxs = list(range(
                s0 // bs, min(len(adopted), -(-(s0 + padded0) // bs))))
        req.hit_tokens = h
        req.adopted_n = len(adopted)
        return plan, adopted, fork_idxs

    def _register_prefix(self, req):
        """Prefill done: register every FULL prompt block for future
        hits (content-addressed; the tail partial block — still
        receiving this request's decode writes — is never shared).
        Blocks already indexed (the adopted prefix, or a concurrent
        twin that registered first) are refreshed, not re-inserted, so
        a shared system prompt stays resident ONCE."""
        if self.prefix is None or self._quarantined:
            return
        nfull = int(req.prompt.size) // self.kv.block_size
        if not nfull:
            return
        try:
            self.prefix.insert(req.prompt, req.version,
                               self.kv.owner_blocks(req.rid)[:nfull])
        except Exception as e:  # noqa: BLE001 — transient = degrade
            # a TRANSIENT failure registering the prefix (injected
            # index fault) costs future hits, never correctness — skip
            if classify_failure(e) != TRANSIENT:
                raise
            _health.emit("prefix_insert_skipped", rid=req.rid,
                         error=f"{type(e).__name__}: {e}")

    # -- swap-based preemption (ISSUE 18) --------------------------------

    def _try_preempt(self, for_req) -> bool:
        """Admission hit block pressure: swap the cheapest
        lower-priority DECODING request out to the host tier so
        ``for_req`` can admit now instead of deferring. The victim's
        pages snapshot at this boundary (the stager fetches them
        asynchronously — immutable functional handles, so freeing the
        device blocks immediately is safe), it re-enters the backlog
        right behind the request it yielded to, and re-admission
        refills and resumes bitwise. Returns True when a victim was
        preempted (the caller retries admission in the same pass)."""
        if not self.preempt_enabled:
            return False
        cands = [r for r in self._active if r.priority < for_req.priority]
        if not cands:
            return False
        # lowest priority first; among equals the fewest owned blocks —
        # the cheapest swap that relieves the pressure
        victim = min(cands, key=lambda r: (r.priority,
                                           self.kv.owned(r.rid)))
        blocks = self.kv.owner_blocks(victim.rid)
        if not blocks:
            return False
        h = self.kv_swap.spill(blocks, tag="preempt")
        if h is None and self.prefix is not None \
                and self.prefix.drop_spilled(len(blocks)):
            # host pressure: a running request's decode state outranks
            # cold spilled prefix chains — drop the coldest and retry
            h = self.kv_swap.spill(blocks, tag="preempt")
        if h is None:
            return False
        victim.swap_handle = h
        # the snapshot keeps the bytes alive for the stager — the
        # device blocks return to the free list at THIS boundary
        self.kv.free(victim.rid)
        if self.draft_kv is not None:
            self.draft_kv.free(victim.rid)
        victim.draft_pos = 0
        self._active.remove(victim)
        self._free_slots.append(victim.slot)
        victim.slot = None
        # behind the head request it yielded to; model version stays
        # pinned — the resumed stream must finish on the params it
        # started with
        self._backlog.insert(1, victim)
        self._bump("preemptions")
        if obs.enabled():
            obs.counter("serve/preemptions").inc()
        _flight.record("serve/preempt", rid=victim.rid,
                       for_rid=for_req.rid, blocks=len(blocks))
        return True

    def _resume_preempted(self, req) -> bool:
        """Refill-before-resume for the backlog head: land the
        preempted request's host pages back in the device pool and
        return it to the running batch — its decode continues from the
        exact position it was interrupted at, bitwise (the refilled
        pages are digest-verified copies of the snapshotted handles —
        the PR-13 replay argument). A stage still in flight, a full
        device pool, or a full draft pool DEFERS (False — retry next
        boundary); a failed/corrupt stage DEGRADES to re-prefilling the
        host-resident tokens through the ordinary chunk schedule (the
        router-failover recompute precedent — per-position KV is
        bitwise stable across chunkings). Returns True when the request
        left the backlog (resumed or recomputing)."""
        spec_over = (self.spec_k + 1) if self.draft_model is not None \
            else 0
        keep = int(req.prompt.size) + req.max_new_tokens + spec_over
        h = req.swap_handle
        if h is not None:
            if h.state == SPILL_PENDING:
                return False   # stage in flight — next boundary
            need = h.n_blocks
            if not self.kv.can_allocate(need) and self.prefix is not None \
                    and not self._quarantined:
                self.prefix.evict(need - self.kv.blocks_free())
            if not self.kv.can_allocate(need):
                return False
            if self.draft_kv is not None and not self.draft_kv.can_allocate(
                    blocks_for_tokens(keep, self.kv.block_size)):
                return False
            try:
                ids = self.kv_swap.refill(req.rid, h)
            except KVCacheOOM:
                return False   # handle intact — roomier boundary retries
            if ids is not None:
                req.swap_handle = None
                # single-threaded admission: the can_allocate pre-check
                # above guarantees this growth cannot OOM
                if self.draft_kv is not None:
                    self.draft_kv.ensure_capacity(req.rid, keep)
                self._backlog.popleft()
                req.slot = self._free_slots.pop()
                self._active.append(req)
                self._bump("resumes")
                if obs.enabled():
                    obs.counter("serve/resumes").inc()
                return True
            # stage failed/corrupt (handle settled by the manager):
            # recompute from the host-resident tokens — the KV for
            # positions [0, pos) re-prefills chunk-by-chunk, then
            # decode continues exactly where it stopped
            req.swap_handle = None
            req.resume_seq = np.concatenate(
                [req.prompt,
                 np.asarray(req.generated, np.int32)])[:req.pos]
        seq = req.resume_seq
        worst = max(prefill_padded_end(seq.size, self.prefill_chunk),
                    keep)
        need = blocks_for_tokens(worst, self.kv.block_size)
        if not self.kv.can_allocate(need) and self.prefix is not None \
                and not self._quarantined:
            self.prefix.evict(need - self.kv.blocks_free())
        if not self.kv.can_allocate(need) or (
                self.draft_kv is not None
                and not self.draft_kv.can_allocate(need)):
            return False   # resume_seq persists — retry stays here
        self.kv.ensure_capacity(req.rid, worst)
        if self.draft_kv is not None:
            self.draft_kv.ensure_capacity(req.rid, worst)
        req.chunks = prefill_schedule(seq.size, self.prefill_chunk)
        req.pf_i = 0
        self._backlog.popleft()
        req.slot = self._free_slots.pop()
        self._prefilling.append(req)
        self._bump("resume_recomputes")
        if obs.enabled():
            obs.counter("serve/resume_recomputes").inc()
        _health.emit("kv_swap_recompute", rid=req.rid,
                     tokens=int(seq.size))
        return True

    def _advance_prefill(self) -> bool:
        """ONE prefill chunk for the head admitted-but-prefilling
        request (FIFO), interleaved with the running batch's decode
        steps — a joining 100k-token prompt stalls active generations
        by at most one chunk's forward per step boundary, not its whole
        prefill. The LAST chunk's final real row is the first generated
        token (TTFT stamps there). Returns True when it did work."""
        if not self._prefilling:
            return False
        req = self._prefilling[0]
        mv = req.model_version
        t0 = time.perf_counter_ns()
        s, real, padded = req.chunks[req.pf_i]
        last = req.pf_i == len(req.chunks) - 1
        # a preempted request whose swap stage failed re-prefills its
        # host-resident prompt+generated tokens (resume_seq) through
        # this same chunk machinery; the first-token readback/emit is
        # skipped — its next token comes from the ordinary decode step
        resumed = req.resume_seq is not None
        src = req.resume_seq if resumed else req.prompt
        # write-safety invariant: every block this chunk touches is
        # PRIVATE — warm suffix chunks start past the adopted prefix,
        # and the rerun-last-chunk case's shared blocks were forked
        # copy-on-write inside the admission transaction (_admit)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :real] = src[s:s + real]

        def dispatch():
            _chaos.maybe_fire("serving/prefill", tag=self.name)
            with obs.span("serve/prefill", rid=req.rid, chunk=req.pf_i,
                          of=len(req.chunks), version=req.version):
                table = self.kv.block_table(req.rid)[None]
                choices, pages = self._step_jit(
                    mv.params, self.kv.pages(), self._put(toks),
                    self._put(np.asarray([s], np.int32)),
                    self._put(table), *self._sampling_args([req], 1))
                dpages = None
                if self.draft_kv is not None and req.hit_tokens == 0:
                    # warm prefix-HIT requests skip the draft prefill
                    # with the target's (the adopted region was never
                    # prefilled here) — the draft catches up LAZILY on
                    # the row's first speculative round instead
                    # (_draft_catchup), so a warm hit keeps its spec
                    # eligibility
                    dtable = self.draft_kv.block_table(req.rid)[None]
                    _, dpages = self._draft_jit(
                        self._draft_params(), self.draft_kv.pages(),
                        self._put(toks),
                        self._put(np.asarray([s], np.int32)),
                        self._put(dtable), *self._sampling_args((), 1))
                first_tok = None
                if last and not resumed:
                    # sync-ok: the first generated token — the client's
                    # TTFT — is exactly this readback
                    first_tok = int(np.asarray(choices)[0, real - 1])
                return first_tok, pages, dpages

        first_tok, pages, dpages = self._replay_group(
            "prefill", [req], dispatch)
        self.kv.set_pages(pages)
        if dpages is not None:
            self.draft_kv.set_pages(dpages)
            req.draft_pos = s + real
        self._bump("prefill_chunks")
        req.pf_i += 1
        req.prefill_ms += (time.perf_counter_ns() - t0) / 1e6
        if not last:
            return True
        self._prefilling.popleft()
        self._register_prefix(req)
        # the admission reservation covered the PREFILL's padded chunk
        # tail (prefill_padded_end), which can exceed the generation
        # phase's exact need — return the padding-only tail blocks to
        # the pool now (per-row ledger truncate, refcount-aware: the
        # adopted prefix sits at the table HEAD and is untouched).
        # Nothing re-grows this row's tables afterwards — decode/spec
        # writes are bounded by keep (verify tops out at
        # pos + spec_k < keep, catch-up clamps to the owned capacity) —
        # so the no-mid-flight-OOM invariant keeps holding while
        # backlogged admissions see the reclaimed blocks immediately.
        spec_over = (self.spec_k + 1) if self.draft_model is not None \
            else 0
        keep = int(req.prompt.size) + req.max_new_tokens + spec_over
        self.kv.truncate(req.rid, keep)
        if self.draft_kv is not None:
            self.draft_kv.truncate(req.rid, keep)
        if resumed:
            # recompute complete: KV for [0, pos) is rebuilt (bitwise —
            # per-position KV is chunking-stable), decode picks up with
            # generated[-1] at pos exactly as if never interrupted. No
            # first-token emit, no TTFT restamp — the client already
            # has these tokens.
            req.pos = int(req.resume_seq.size)
            req.resume_seq = None
            self._active.append(req)
            return True
        req.pos = int(req.prompt.size)
        req.t_first_ns = time.perf_counter_ns()
        self._bump("tokens")
        if obs.enabled():
            obs.histogram("serve/prefill_ms", unit="ms").observe(
                req.prefill_ms)
            obs.histogram("serve/ttft_ms", unit="ms").observe(
                (req.t_first_ns - req.t_enqueue_ns) / 1e6)
            obs.counter("serve/lm_tokens").inc()
        self._active.append(req)
        self._emit(req, first_tok)
        return True

    def _draft_params(self):
        return self.draft_model.params

    def _emit(self, req, token) -> bool:
        """Append one generated token; returns True when the request is
        DONE (eos or budget) and has been finished+released."""
        req.generated.append(int(token))
        done = (req.eos_id is not None and int(token) == req.eos_id) \
            or len(req.generated) >= req.max_new_tokens
        if done:
            self._finish(req)
        return done

    def _step_all(self) -> bool:
        """One decode dispatch per active version group (admission
        order). Each dispatch pads its rows to a power-of-two bucket
        (floor 2) of the FIXED slot capacity; padded slots carry the
        null block table, so their writes land in garbage space."""
        if not self._active:
            return False
        groups = {}
        for r in self._active:
            groups.setdefault(r.version, []).append(r)
        for version, rows in list(groups.items()):
            n_elig = sum(1 for r in rows if r.temperature <= 0.0)
            if self.draft_model is not None and n_elig >= 1 \
                    and 2 * n_elig >= len(rows) and not self._prefilling:
                # a GREEDY-MAJORITY group with no prompt mid-prefill
                # rides ONE batched speculative round — greedy rows
                # draft+verify spec_k tokens, sampled rows (argmax-match
                # acceptance cannot apply) ride the same verify dispatch
                # masked to one real token. Two deliberate guards: a
                # sampled-majority group steps plain (each sampled row
                # advances 1 token per round, so a lone greedy row must
                # not tax the majority spec_k+2 dispatches per token),
                # and a multi-token spec burst must not delay a joining
                # request's interleaved prefill chunks (the PR-8 rule;
                # the resulting draft-cache lag is repaid by
                # _draft_catchup on the next round). Spec is
                # output-preserving, so tokens are bitwise the plain
                # step's either way.
                self._spec_step(version, rows)
            else:
                if self.draft_model is not None and rows:
                    # armed but not speculating this boundary (sampled
                    # majority, or prefill-interleave protection):
                    # plain step, counted so operators can see
                    # speculation capacity going unused
                    self._bump("spec_fallbacks")
                    if obs.enabled():
                        obs.counter("serve/spec_fallbacks").inc()
                self._step_group(version, rows)
        return True

    def _step_group(self, version, rows):
        n = len(rows)
        bucket = bucket_for(max(n, 2), self.max_slots)
        tokens = np.zeros((bucket, 1), np.int32)
        positions = np.zeros((bucket,), np.int32)
        tables = np.zeros((bucket, self.kv.max_blocks_per_seq), np.int32)
        for i, r in enumerate(rows):
            tokens[i, 0] = r.generated[-1]
            positions[i] = r.pos
            tables[i] = self.kv.block_table(r.rid)
        mv = rows[0].model_version
        rids = [r.rid for r in rows]

        def dispatch():
            _chaos.maybe_fire("serving/scheduler_step", tag=self.name)
            with obs.span("serve/decode_step", rids=rids, bucket=bucket,
                          version=version):
                choices, pages = self._step_jit(
                    mv.params, self.kv.pages(), self._put(tokens),
                    self._put(positions), self._put(tables),
                    *self._sampling_args(rows, bucket))
                # sync-ok: the per-step token readback — EOS detection
                # and per-client streaming both need the ids on host;
                # this is the one deliberate sync of the decode loop
                return np.asarray(choices)[:, 0], pages

        toks, pages = self._replay_group("decode", rows, dispatch)
        self.kv.set_pages(pages)
        self._bump("decode_steps")
        self._bump("tokens", n)
        for i, r in enumerate(rows):
            r.pos += 1
            r.steps += 1
            self._emit(r, toks[i])
        if obs.enabled():
            obs.counter("serve/decode_steps").inc()
            obs.counter("serve/lm_tokens").inc(n)
            obs.histogram("serve/decode_occupancy").observe(n / bucket)
            obs.gauge("serve/active_slots").set(len(self._active))

    def _draft_catchup(self, req, dparams):
        """Bring one row's draft cache level with its target cache:
        re-prefill positions ``draft_pos..pos-1`` from the tokens the
        row already holds (prompt + generated — all host-resident), in
        the prefill chunk shapes warmup compiled. Two callers leave a
        row trailing: a warm prefix HIT (its draft prefill was skipped
        along with the target's — this is the lazy re-prefill that
        restores spec eligibility, ISSUE 14 satellite) and plain decode
        steps taken while the row was spec-ineligible company or a
        prompt was mid-prefill. The tail chunk's pow-2 padding halves
        until it fits the row's OWNED draft capacity (shrunk to the
        exact generation need once its prefill-padding tail was
        truncated), so a padded write can never run past the row's
        block table."""
        seq = np.concatenate([req.prompt,
                              np.asarray(req.generated, np.int32)])
        dtable = self.draft_kv.block_table(req.rid)[None]
        cap = min(self.max_seq_len,
                  self.draft_kv.owned(req.rid) * self.draft_kv.block_size)
        while req.draft_pos < req.pos:
            real = min(self.prefill_chunk, req.pos - req.draft_pos)
            padded = _pow2_bucket(real, self.prefill_chunk)
            while req.draft_pos + padded > cap:
                padded >>= 1
            real = min(real, padded)
            toks = np.zeros((1, padded), np.int32)
            toks[0, :real] = seq[req.draft_pos:req.draft_pos + real]
            _, dpages = self._draft_jit(
                dparams, self.draft_kv.pages(), self._put(toks),
                self._put(np.asarray([req.draft_pos], np.int32)),
                self._put(dtable), *self._sampling_args((), 1))
            self.draft_kv.set_pages(dpages)
            req.draft_pos += real

    def _spec_step(self, version, rows):
        """ONE batched speculative round for a whole version group
        (ISSUE 14 — the generalization of the PR-8 solo fast path):

        1. eligible rows (greedy) that trail the draft cache catch up
           (:meth:`_draft_catchup`);
        2. ``spec_k+1`` BATCHED paged draft steps propose per-row draft
           chains — the token feed stays device-resident (each step
           consumes the previous step's choices), so the draft phase
           adds ZERO readbacks; the extra (k+1)-th step writes d_k's
           K/V so a fully-accepted round leaves no draft-cache hole
           (nn/speculative.py); ineligible rows ride the draft steps
           against the null table (their draft cache is never touched);
        3. ONE chunked verify — the same compiled paged step at
           ``S = spec_k+1`` — scores every row's ``[last, d_1..d_k]``;
        4. per-row acceptance lengths come back from the in-program
           ``batched_acceptance`` schedule in a single readback, and
           each row emits its accepted prefix + the target's own choice
           at the divergence (ineligible rows: acceptance 0 — exactly
           their plain one-token step, bitwise).

        Rollback is positional: row ``b`` advances ``pos`` by
        ``j_b + 1`` while the round wrote ``spec_k+1`` positions —
        rejected positions hold garbage that position-masked paged
        attention never reads and the next round's (or plain step's)
        writes overwrite, in BOTH pools (``draft_pos`` snaps to ``pos``
        so the pools stay in lockstep). Admission already reserved the
        ``spec_k+1`` overshoot per slot (``spec_over``), so the round's
        writes can never OOM. Output-preserving: every emitted token is
        the target's own choice at its position — the bitwise gate in
        tests/test_serving_lm.py holds per row across any batch mix."""
        k = self.spec_k
        n = len(rows)
        bucket = bucket_for(max(n, 2), self.max_slots)
        elig = np.zeros((bucket,), bool)
        last = np.zeros((bucket, 1), np.int32)
        positions = np.zeros((bucket,), np.int32)
        dpositions = np.zeros((bucket,), np.int32)
        tables = np.zeros((bucket, self.kv.max_blocks_per_seq), np.int32)
        dtables = np.zeros((bucket, self.draft_kv.max_blocks_per_seq),
                           np.int32)
        for i, r in enumerate(rows):
            elig[i] = r.temperature <= 0.0
            last[i, 0] = r.generated[-1]
            positions[i] = r.pos
            tables[i] = self.kv.block_table(r.rid)
        mv = rows[0].model_version
        rids = [r.rid for r in rows]
        dparams = self._draft_params()
        samp = self._sampling_args(rows, bucket)
        greedy = self._sampling_args((), bucket)

        def round_fn():
            _chaos.maybe_fire("serving/spec_round", tag=self.name)
            with obs.span("serve/spec_round", rids=rids, k=k,
                          bucket=bucket, version=version):
                for i, r in enumerate(rows):
                    if elig[i] and r.draft_pos < r.pos:
                        self._draft_catchup(r, dparams)
                    if elig[i]:
                        # fetched AFTER catch-up — tables are stable
                        # within a round, but keep one read order
                        dtables[i] = self.draft_kv.block_table(r.rid)
                        dpositions[i] = r.pos
                tok = self._put(last)
                last_dev = tok
                dtab_dev = self._put(dtables)
                drafts = []
                for i in range(k + 1):
                    choices, dpages = self._draft_jit(
                        dparams, self.draft_kv.pages(), tok,
                        self._put(dpositions + i), dtab_dev, *greedy)
                    self.draft_kv.set_pages(dpages)
                    tok = choices
                    if i < k:
                        drafts.append(choices)
                drafts_c = jnp.concatenate(drafts, axis=1)   # (B, k)
                chunk = jnp.concatenate([last_dev, drafts_c], axis=1)
                vchoices, pages = self._step_jit(
                    mv.params, self.kv.pages(), chunk,
                    self._put(positions), self._put(tables), *samp)
                self.kv.set_pages(pages)
                j, emit = self._accept_jit(drafts_c, vchoices,
                                           self._put(elig))
                # sync-ok: the per-round readback — acceptance lengths
                # + emitted tokens drive EOS/budget bookkeeping on host
                return jax.device_get((j, emit))

        # the replay snapshot covers BOTH pools' page handles and every
        # row's (pos, draft_pos, generated) — a transient anywhere in
        # the round (catch-up, draft burst, verify) rolls the whole
        # round back and replays it bitwise
        j, emit = self._replay_group("spec", rows, round_fn)
        self._bump("decode_steps")
        self._bump("spec_rounds")
        nrow = nacc = ntok = 0
        for i, r in enumerate(rows):
            ji = int(j[i])
            r.pos += ji + 1
            r.steps += 1
            if elig[i]:
                r.draft_pos = r.pos
                r.spec_rounds += 1
                r.spec_accepted += ji
                nrow += 1
                nacc += ji
                if obs.enabled():
                    obs.histogram("serve/spec_accepted_len").observe(ji)
            for t in emit[i, :ji + 1]:
                ntok += 1
                if self._emit(r, int(t)):
                    break
        self._bump("spec_row_rounds", nrow)
        self._bump("spec_accepted", nacc)
        self._bump("tokens", ntok)
        if obs.enabled():
            obs.counter("serve/spec_rounds").inc()
            obs.counter("serve/spec_accepted").inc(nacc)
            obs.counter("serve/lm_tokens").inc(ntok)

    # -- eviction / completion -------------------------------------------

    def _evict_expired(self):
        now = time.monotonic()
        for r in list(self._active):
            if r.expired(now):
                self._expire(r)
        for r in list(self._prefilling):
            if r.expired(now):
                self._expire(r)
        for r in list(self._backlog):
            if r.expired(now):
                self._backlog.remove(r)
                self._expire(r)

    def _expire(self, req):
        self._bump("timeouts")
        if obs.enabled():
            obs.counter("serve/timeouts").inc()
        exc = DeadlineExceeded(
            f"deadline passed after {len(req.generated)} of "
            f"{req.max_new_tokens} tokens")
        # the tokens generated before eviction are real (and bitwise
        # equal to a solo decode's prefix) — hand them to the client
        exc.partial = np.asarray(req.generated, np.int32)
        self._release(req)
        try:
            req.future.set_exception(exc)
        except Exception:
            pass

    def _finish(self, req, cancel: bool = False):
        req.t_done_ns = time.perf_counter_ns()
        self._release(req)
        if cancel:
            return
        out = np.asarray(req.generated, np.int32)
        n = out.size
        tpot = ((req.t_done_ns - req.t_first_ns) / 1e6 / (n - 1)
                if (req.t_first_ns and n > 1) else 0.0)
        req.future.version = req.version
        req.future.trace = {
            "rid": req.rid,
            "queue_wait_ms": ((req.t_admit_ns or req.t_enqueue_ns)
                              - req.t_enqueue_ns) / 1e6,
            "prefill_ms": round(req.prefill_ms, 3),
            "ttft_ms": ((req.t_first_ns - req.t_enqueue_ns) / 1e6
                        if req.t_first_ns else None),
            "tpot_ms": round(tpot, 3),
            "decode_steps": req.steps,
            "tokens": n,
            "version": req.version,
            "prefix_hit_tokens": req.hit_tokens,
            "spec_rounds": req.spec_rounds,
            "spec_accepted": req.spec_accepted,
        }
        self._bump("completed")
        if obs.enabled():
            obs.counter("serve/lm_completed").inc()
            if tpot:
                obs.histogram("serve/tpot_ms", unit="ms").observe(tpot)
            _flight.record("serve/lm_done", rid=req.rid, tokens=n,
                           steps=req.steps, version=req.version)
        try:
            req.future.set_result(out)
        except Exception:
            pass

    def _release(self, req):
        """Return every engine resource a request holds: its slot, its
        KV blocks (both caches), and any host-tier reservation a
        preemption left behind (the host pool must drain to 0 at every
        shutdown path, like the device pool). Safe to call twice."""
        if req in self._active:
            self._active.remove(req)
        if req in self._prefilling:
            self._prefilling.remove(req)
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None
        self.kv.free(req.rid)
        if self.draft_kv is not None:
            self.draft_kv.free(req.rid)
        if req.swap_handle is not None:
            self.kv_swap.discard(req.swap_handle)
            req.swap_handle = None
        req.resume_seq = None
        req.model_version = None

    # -- internals -------------------------------------------------------

    def _on_done(self, future):
        with self._cond:
            self._pending -= 1
            self._cond.notify_all()

    def _bump(self, key: str, n: int = 1):
        with self._stats_lock:
            self._stats[key] += n


def decode_scheduler_threads_alive() -> int:
    """Live scheduler threads (tests assert 0 after shutdown)."""
    return sum(1 for t in threading.enumerate()
               if t.name == THREAD_NAME and t.is_alive())
