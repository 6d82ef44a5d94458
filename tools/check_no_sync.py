#!/usr/bin/env python3
"""Lint: the training hot loop must not grow un-annotated host<->device
sync points.

PR 1's spans showed the step loop was host-bound partly because of a
blocking ``float(loss)`` every iteration; PR 2 restructured the loop so
every remaining sync is deliberate. This check keeps it that way: inside
the hot-loop functions listed below, any ``float(...)`` call or
``.block_until_ready(`` use must carry a ``# sync-ok: <reason>``
annotation on the same line or the line above — an un-annotated sync is
a build failure, not a silent 2x step-time regression six PRs later.

Run: ``python tools/check_no_sync.py`` (wired as ``make check-no-sync``,
a prerequisite of ``make tier1``).
"""
from __future__ import annotations

import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# file -> function names whose bodies form the training hot path
HOT_FUNCS = {
    "bigdl_tpu/optim/optimizer.py": {
        # the ONE epoch loop for any K, the ONE accounting of a resolved
        # loss (NaN policy, flight record, monitor, summaries), and the
        # ONE builder of the compiled step with what a mode puts into it
        "optimize", "_optimize_impl", "_run_epoch", "_account_loss",
        "_clamp_superstep", "_observe_loss", "_drain_pending_losses",
        "_build_step", "_step_mode", "_sparse_exchange",
        "_stage_minibatch", "_host_xy", "_stage_group",
        "_place_batch", "_place_group",
        # self-healing paths that run inside the step loop: the guarded
        # dispatch (its host snapshot is the one deliberate per-dispatch
        # fetch, taken only when a FaultPolicy is armed) and the Tier-1
        # remediation tick (host-side control only — it may never add a
        # readback beyond what the sync policy already resolved)
        "_dispatch_guarded", "_host_step_state", "_check_halt",
        "_remediation_tick", "_apply_anomaly_events",
        "_tighten_stall_deadline",
    },
    "bigdl_tpu/optim/staging.py": {"_run", "__next__"},
    # health/flight hot paths: beacon pulses, anomaly observation and
    # flight-ring appends run INSIDE the step loop when observability
    # is on — none of them may touch a device value
    "bigdl_tpu/observability/health.py": {"pulse", "observe",
                                          "maybe_tick", "emit"},
    "bigdl_tpu/observability/flight.py": {"record"},
    # perf introspection hot hooks: the instrumented dispatch wrapper
    # and the per-step MFU/phase math run inside the step loop — all
    # host arithmetic on already-resolved floats, never a device touch
    "bigdl_tpu/observability/perf.py": {"__call__", "_key", "note",
                                        "note_step"},
    # cluster snapshot cadence check runs per iteration (the write
    # itself is host JSON on an elapsed cadence)
    "bigdl_tpu/observability/cluster.py": {"maybe_write"},
    # forward-only loops: device-side metric/output accumulation means
    # the per-batch body must stay sync-free (one readback per epoch)
    "bigdl_tpu/optim/evaluator.py": {
        "_evaluate_device", "_stage_device", "_stage",
    },
    "bigdl_tpu/optim/predictor.py": {"_iter_outputs", "predict", "_stage"},
    # serving batcher hot loop: a stray sync between dispatches stalls
    # every queued client, not just one training step (the readback in
    # _dispatch and the warmup block are the two deliberate ones);
    # _place_batch/_bucket_for are the mesh dispatch path — the padded
    # batch shards onto the mesh with a transfer, never a block
    "bigdl_tpu/serving/engine.py": {
        "_batcher", "_collect", "_dispatch", "submit", "warmup",
        "_place_batch", "_bucket_for",
    },
    "bigdl_tpu/serving/batching.py": {"assemble"},
    # continuous-batching decode loop: a stray sync between decode steps
    # stalls EVERY active generation, not one request — the deliberate
    # ones are the per-step token readback (EOS detection), the
    # first-token readback in prefill, the batched spec round's single
    # acceptance readback (the draft burst itself is device-resident —
    # a sync inside it would serialize every proposal), and the warmup
    # precompile block
    "bigdl_tpu/serving/decode_scheduler.py": {
        "_loop", "_admit", "_advance_prefill", "_step_all", "_step_group",
        "_spec_step", "_draft_catchup", "_evict_expired", "_emit",
        "_finish", "_release",
        "submit", "warmup", "_put", "_sampling_args",
        # prefix-reuse admission path (ISSUE 12): the chain lookup,
        # warm-plan construction and suffix registration are pure host
        # hashing/bookkeeping at every step boundary
        "_prefix_plan", "_register_prefix", "cached_prefix_tokens",
        # transient step replay + ledger auditor (ISSUE 13): the
        # per-dispatch snapshot is reference/int copies, the restore
        # swaps page HANDLES, and the audit is pure ledger arithmetic —
        # none may grow a device sync (the replay guard wraps the hot
        # dispatch of every decode step)
        "_snapshot_step_state", "_restore_step_state", "_replay_group",
        "audit", "_audit", "_triage",
        # swap-based preemption (ISSUE 18): both run at step boundaries
        # inside the admission loop — the spill is a handle snapshot +
        # enqueue (the fetch is the stager thread's), the resume issues
        # the refill scatter without blocking on it
        "_try_preempt", "_resume_preempted",
    },
    # block ledger: admission-control bookkeeping runs between decode
    # steps and must stay pure host state (device pages are functional
    # handles — defrag and the copy-on-write fork, both explicit rare
    # operations, are the only page-touching paths and they issue
    # transfers without ever BLOCKING on one)
    "bigdl_tpu/serving/kv_cache.py": {
        "ensure_capacity", "free", "block_table", "can_allocate",
        "adopt", "retain", "release", "fork_blocks", "block_refs",
        "owner_blocks", "truncate",
        # the invariant checker runs on the scheduler cadence — one
        # consistent host snapshot, never a page read
        "audit",
        # cross-process handoff primitives (ISSUE 15): export's ONE
        # deliberate page fetch is jax.device_get (the handoff's data
        # hop); adopt issues scatter transfers without blocking
        "export_blocks", "adopt_serialized",
        # host-RAM paging tier (ISSUE 18): the boundary-scheduled swap
        # paths — spill captures handles and enqueues (the fetch lives
        # on the stager thread, NOT here), refill verifies + adopts
        # (issues the scatter, never blocks on it), and the staging-
        # ring placement only copies into reusable host buffers (the
        # ring's reuse fence is annotated in native/)
        "snapshot_blocks", "spill", "spill_many", "refill",
        "refill_many", "_stage",
    },
    # fleet transport (ISSUE 15): framed send/recv on router dispatch
    # and agent reply paths — pure socket/bytes work, a device touch
    # here would stall every in-flight fleet request on the connection
    "bigdl_tpu/serving/transport.py": {
        "request_async", "_send_frame", "_recv_frame", "_recv_loop",
        "pack_arrays", "unpack_arrays",
    },
    # fleet layer (ISSUE 15): the agent's beat loop runs on a cadence
    # next to a live engine; RemoteReplica.submit runs inside the
    # router's dispatch loop; the export/adopt handlers run on
    # transport threads between the engine's dispatches — all host
    # bookkeeping (export's page fetch lives in kv_cache.export_blocks)
    "bigdl_tpu/serving/fleet.py": {
        "_beat_loop", "_serving_section", "_member_doc", "submit",
        "_export_prefix", "_adopt_prefix", "_op_submit",
        "cached_prefix_tokens", "_handoff",
    },
    # prefix cache: content-addressed index over the ledger — digest
    # walks and LRU bookkeeping inside the admission loop (and under
    # router dispatch threads via peek); a sync here would stall every
    # admission on the box
    "bigdl_tpu/serving/prefix_cache.py": {
        "lookup", "peek", "insert", "evict", "chain_keys", "_walk",
        "_on_remap", "pinned_blocks",
        # second-chance paths (ISSUE 18): lookup's spilled-chain
        # continuation and host-pool pressure relief run inside the
        # admission loop — host hashing/bookkeeping plus non-blocking
        # refill dispatch only
        "_refill_run", "drop_spilled",
    },
    # router hot loop: pure host routing — a sync here would stall
    # EVERY class queue; the replicas' own batcher threads do the
    # device work. _on_inner_done runs on replica threads between
    # their dispatches and must stay host-only too.
    "bigdl_tpu/serving/router.py": {
        "_route_loop", "_drr_round", "_dispatch_one", "_on_inner_done",
        "_failover", "_drain_replica", "submit",
        # prefix-affinity pick: N digest-walk probes per dispatch —
        # host hashing only, never a device value
        "_affinity_pick",
        # KV-preserving failover splice: numpy concatenation of host
        # int arrays on the inner-done callback path (runs on replica
        # threads between THEIR dispatches)
        "_recover_decode", "_reseed_ewma_locked", "_complete",
    },
    # elastic control plane (ISSUE 19): the reconcile tick runs on a
    # cadence BESIDE the data plane — scoring is arithmetic over stats
    # dicts the replicas already published, scale/promote/victim moves
    # are socket RPCs + pool bookkeeping, and the prefix warm rides the
    # existing export/adopt handoff; a device touch here would stall
    # reconciliation behind a readback and couple control-plane health
    # to device health
    "bigdl_tpu/serving/controller.py": {
        "tick", "_score", "_serving", "_router_size", "_scale_up",
        "_scale_down", "_pick_victim", "_reconcile_prefill",
        "_promote", "_demote", "_warm", "adopt", "_register",
    },
    # mesh dispatch path: the sharded version load (publish, on the
    # swapping caller's thread) issues device transfers but must never
    # BLOCK on one — traffic flows on the active version meanwhile
    "bigdl_tpu/serving/registry.py": {"publish", "_place_tree"},
    # paged-attention dispatch seam (ISSUE 11): trace-time code on the
    # decode hot path — mode resolution, the shard_map wrapper and the
    # kernel builder run inside the compiled step's trace and must
    # never touch a device value (a sync here would serialize every
    # warmup/first-shape compile behind a readback)
    "bigdl_tpu/parallel/flash.py": {"paged_attention", "paged_mode"},
    # fault-injection plane (ISSUE 13): maybe_fire sits on EVERY hot
    # seam above — disarmed it must stay one module-global read, armed
    # it is host bookkeeping + a typed raise/sleep, never a device
    # touch
    "bigdl_tpu/parallel/chaos.py": {"maybe_fire"},
    "bigdl_tpu/kernels/paged_attention.py": {"paged_decode_attention"},
    "bigdl_tpu/nn/attention.py": {"decode_paged", "_paged_gather_attend"},
}

SYNC = re.compile(r"(?<![\w.])float\(|\.block_until_ready\(")
OK = re.compile(r"#\s*sync-ok\s*:")


def _hot_ranges(tree, wanted):
    """(name, first_line, last_line) for every wanted def, however nested."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node.name in wanted:
            out.append((node.name, node.lineno, node.end_lineno))
    return out


def check(repo: str = REPO):
    violations = []
    for rel, wanted in HOT_FUNCS.items():
        path = os.path.join(repo, rel)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        lines = src.splitlines()
        found = set()
        for name, lo, hi in _hot_ranges(ast.parse(src), wanted):
            found.add(name)
            for i in range(lo, hi + 1):
                line = lines[i - 1]
                if not SYNC.search(line):
                    continue
                prev = lines[i - 2] if i >= 2 else ""
                if OK.search(line) or OK.search(prev):
                    continue
                violations.append(
                    f"{rel}:{i}: un-annotated sync point in {name}(): "
                    f"{line.strip()}")
        missing = wanted - found
        if missing:
            violations.append(
                f"{rel}: hot functions not found (lint out of date — "
                f"update HOT_FUNCS): {sorted(missing)}")
    return violations


def main():
    violations = check()
    if violations:
        print("check_no_sync: FAIL — a sync point in the step loop stalls "
              "the device pipeline.\nAnnotate deliberate syncs with "
              "'# sync-ok: <reason>' (same line or the line above):\n")
        for v in violations:
            print("  " + v)
        return 1
    print("check_no_sync: ok — every hot-loop sync point is annotated")
    return 0


if __name__ == "__main__":
    sys.exit(main())
