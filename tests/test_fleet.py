"""Cross-process fleet serving (ISSUE 15).

The gates: a replica in ANOTHER process serves tokens bitwise-identical
to the in-process scheduler (greedy and seeded-sampled); the two-phase
fleet swap extends over the process boundary without mixing versions;
an agent process dying mid-decode loses ZERO requests (its typed
partials splice through the router's KV-preserving failover, bitwise
the uninterrupted stream); a prefill-specialist → decode-specialist KV
handoff produces tokens bitwise the monolithic scheduler; and a corrupt
or version-skewed handoff is REFUSED typed before any page lands.

Process discipline follows tests/multihost_util.py: agents spawn as
real subprocesses (their own jax runtimes — no cross-process
collectives needed, only sockets + files); a box whose environment
cannot spawn/run them SKIPS rather than fails.
"""
import json
import os
import pickle
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import jax

from bigdl_tpu import observability as obs
from bigdl_tpu.observability import health as _health
from bigdl_tpu.models.transformer_lm import TransformerLM
from bigdl_tpu.serving import (DecodeScheduler, DisaggregatedFleet,
                               EngineStopped, FleetMonitor, KVCacheOOM,
                               KVHandoffError, PriorityClass,
                               RemoteReplica, ReplicaAgent, Router,
                               TransportClient, TransportServer,
                               transport_threads_alive, wait_for_members)
from bigdl_tpu.serving.fleet import (fleet_threads_alive, read_member,
                                     warm_replica)
from bigdl_tpu.serving.kv_cache import SPILL_PENDING
from bigdl_tpu.serving.transport import (RemoteError, decode_tree,
                                         encode_tree)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V, H = 48, 32
SCHED = dict(max_slots=4, block_size=4, max_seq_len=96, prefill_chunk=8)
MODEL = dict(vocab_size=V, hidden_size=H, num_heads=4, filter_size=64,
             num_layers=2, max_len=256)


@pytest.fixture(autouse=True)
def _clean_health():
    yield
    _health.reset()
    obs.registry().reset()
    obs.disable()


def _model():
    m = TransformerLM(**MODEL)
    m.ensure_initialized()
    return m


def _prompts(rng, sizes):
    return [rng.randint(1, V, size=n).astype(np.int32) for n in sizes]


# -- subprocess plumbing ----------------------------------------------------

def _save_params(model, fleet_dir):
    path = os.path.join(fleet_dir, "params.pkl")
    with open(path, "wb") as f:
        pickle.dump(jax.tree_util.tree_map(np.asarray, model.params), f)
    return path


def _spawn_agent(fleet_dir, name, params_path, *, role="replica",
                 tags=(), chaos=None, idx=1, sched=None):
    cfg = {"fleet_dir": fleet_dir, "name": name, "role": role,
           "tags": list(tags), "beat_s": 0.15, "process_index": idx,
           "model": MODEL, "params_path": params_path,
           "scheduler": dict(SCHED, **(sched or {}))}
    if chaos:
        cfg["chaos"] = chaos
    cfg_path = os.path.join(fleet_dir, f"cfg_{name}.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("BIGDL_TPU_CHAOS", None)
    # log FILES, not pipes: nothing drains a pipe mid-test, so a chatty
    # agent (jax warnings, death tracebacks) would block on the ~64 KB
    # pipe buffer and wedge the drill
    log = open(os.path.join(fleet_dir, f"agent_{name}.log"), "w")
    p = subprocess.Popen(
        [sys.executable, "-m", "bigdl_tpu.serving.fleet", cfg_path],
        stdout=log, stderr=subprocess.STDOUT, cwd=REPO, env=env)
    p._bigdl_log = os.path.join(fleet_dir, f"agent_{name}.log")
    return p


def _members_or_skip(fleet_dir, names, procs, timeout_s=240.0):
    """Wait for the spawned agents' membership files; SKIP (not fail)
    when the box provably cannot run agent subprocesses at all."""
    try:
        return wait_for_members(fleet_dir, names, timeout_s=timeout_s)
    except TimeoutError as e:
        def tail(p):
            try:
                with open(p._bigdl_log) as f:
                    return f.read()[-800:]
            except OSError:
                return "<unreadable>"
        dead = [(p.poll(), tail(p)) for p in procs
                if p.poll() is not None]
        for p in procs:
            if p.poll() is None:
                p.kill()
        if dead:
            pytest.skip(f"agent subprocess unusable on this box: {dead}")
        raise e


def _reap(procs, timeout=60):
    """Wait for clean agent exits; escalate to kill only on a hang."""
    codes = []
    for p in procs:
        try:
            codes.append(p.wait(timeout=timeout))
        except subprocess.TimeoutExpired:
            p.kill()
            codes.append(None)
    return codes


def _end(procs, grace=60):
    """finally-block cleanup: give agents their grace to exit on their
    own (the shutdown RPC reply races their process exit), then force."""
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                p.terminate()


# -- transport (in-process) -------------------------------------------------

def test_transport_roundtrip_arrays_errors_and_pytree_codec():
    got = {}

    def handler(reply, op, meta, arrays):
        if op == "echo":
            reply(meta={"sum": float(sum(a.sum() for a in arrays)),
                        "meta": meta}, arrays=arrays)
        elif op == "boom":
            err_arrays = [np.arange(3, dtype=np.int32)]
            reply(error={"type": "EngineStopped", "msg": "dead"},
                  meta={"has_partial": True}, arrays=err_arrays)
        else:
            raise ValueError(f"nope: {op}")

    srv = TransportServer(handler, name="t").start()
    cli = TransportClient("127.0.0.1", srv.port, name="t").connect()
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    b = np.arange(5, dtype=np.int32)
    meta, arrays = cli.request("echo", {"k": 1}, [a, b], timeout=10)
    assert meta["sum"] == float(a.sum() + b.sum())
    assert np.array_equal(arrays[0], a) and np.array_equal(arrays[1], b)
    assert arrays[0].dtype == a.dtype

    with pytest.raises(RemoteError) as ei:
        cli.request("boom", timeout=10)
    assert ei.value.type_name == "EngineStopped"
    assert np.array_equal(ei.value.arrays[0], np.arange(3))
    # a handler exception answers typed instead of killing the conn
    with pytest.raises(RemoteError, match="nope"):
        cli.request("wat", timeout=10)
    meta, _ = cli.request("echo", {}, [], timeout=10)  # conn survives

    # pytree codec round-trip (the publish wire format)
    tree = {"w": np.ones((2, 3), np.float32),
            "inner": {"b": np.zeros((4,), np.int32), "lr": 0.5,
                      "t": (np.full((1,), 7.0), None)},
            "l": [np.arange(2)]}
    bufs = []
    spec = encode_tree(tree, bufs)
    back = decode_tree(json.loads(json.dumps(spec)), bufs)
    assert back["inner"]["lr"] == 0.5 and back["inner"]["t"][1] is None
    assert isinstance(back["inner"]["t"], tuple)
    assert np.array_equal(back["w"], tree["w"])
    assert np.array_equal(back["l"][0], tree["l"][0])

    cli.close()
    srv.close()
    assert transport_threads_alive() == 0, got


# -- KV handoff primitives + typed refusals (in-process) --------------------

def test_kv_export_adopt_primitives_and_geometry_refusal():
    m = _model()
    a = DecodeScheduler(m, name="exp", **SCHED)
    b = DecodeScheduler(m, name="imp", **SCHED)
    a.kv.ensure_capacity("o1", 16)
    ids = a.kv.owner_blocks("o1")
    ids2, layers = a.kv.export_blocks(owner="o1")
    assert ids2 == ids and len(layers) == a.kv.n_layers
    assert layers[0][0].shape[0] == len(ids)
    new = b.kv.adopt_serialized("x", layers)
    assert len(new) == len(ids) and b.kv.blocks_in_use() == len(ids)
    b.kv.free("x")
    assert b.kv.blocks_in_use() == 0
    # geometry refusal: wrong head_dim
    bad = [(np.zeros((2, layers[0][0].shape[1], SCHED["block_size"], 3),
            np.float32),) * 2 for _ in range(a.kv.n_layers)]
    with pytest.raises(ValueError, match="geometry"):
        b.kv.adopt_serialized("y", bad)
    # all-or-nothing under OOM
    big = [(np.zeros((1000,) + layers[0][0].shape[1:], np.float32),) * 2
           for _ in range(a.kv.n_layers)]
    with pytest.raises(KVCacheOOM):
        b.kv.adopt_serialized("z", big)
    assert b.kv.blocks_in_use() == 0
    # exporting a dead block refused
    a.kv.free("o1")
    with pytest.raises(ValueError, match="dead block"):
        a.kv.export_blocks(blocks=ids)


def test_corrupt_and_version_skewed_handoff_refused_typed():
    """The acceptance-criterion refusal matrix, over the REAL agent
    handlers (in-process agents — sockets, two schedulers): tampered
    tokens (chain-hash mismatch), tampered pages (digest mismatch), and
    a version-skewed receiver all refuse typed KVHandoffError with
    ZERO pages adopted; the untampered handoff then lands."""
    m = _model()
    fd = tempfile.mkdtemp(prefix="fleet_refuse_")
    pf = ReplicaAgent(DecodeScheduler(m, name="pf", **SCHED),
                      fleet_dir=fd, name="pf", role="prefill").start()
    dc = ReplicaAgent(DecodeScheduler(m, name="dc", **SCHED),
                      fleet_dir=fd, name="dc", role="decode").start()
    try:
        dpf, ddc = wait_for_members(fd, ["pf", "dc"], timeout_s=20)
        rpf = RemoteReplica(dpf, fleet_dir=fd).start()
        rdc = RemoteReplica(ddc, fleet_dir=fd).start()
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, V, size=35).astype(np.int32)
        meta, arrays = rpf.prefill_export(prompt, timeout=120)
        assert meta["tokens"] == 32  # hit_align(8)-aligned prefix
        hand = {"version": meta["version"], "keys": meta["keys"],
                "geometry": meta["geometry"], "digest": meta["digest"]}

        # (a) corrupt TOKENS → chain-hash mismatch, refused typed
        bad_tok = [arrays[0].copy()] + arrays[1:]
        bad_tok[0][3] ^= 1
        with pytest.raises(KVHandoffError, match="chain-hash"):
            rdc.adopt_prefix(hand, bad_tok, timeout=60)
        # (b) corrupt PAGE BYTES → digest mismatch, refused typed
        bad_pg = [arrays[0]] + [a.copy() for a in arrays[1:]]
        bad_pg[1].reshape(-1)[0] += 1.0
        with pytest.raises(KVHandoffError, match="digest"):
            rdc.adopt_prefix(hand, bad_pg, timeout=60)
        # (c) version skew: decode replica swapped past the export
        p2 = jax.tree_util.tree_map(lambda x: x * 1.01, m.params)
        rdc.registry.publish(p2, version="v-new")
        rdc.registry.activate("v-new")
        with pytest.raises(KVHandoffError, match="version skew"):
            rdc.adopt_prefix(hand, arrays, timeout=60)
        st = rdc.stats()
        assert st["kv"]["blocks_in_use"] == 0, \
            "refused handoffs must adopt ZERO pages"
        # (d) the clean handoff under the matching version lands
        rdc.registry.activate(meta["version"])
        out = rdc.adopt_prefix(hand, arrays, timeout=60)
        assert out[0]["adopted_blocks"] == 32 // SCHED["block_size"]
        assert rdc.stats()["kv"]["blocks_in_use"] == \
            out[0]["adopted_blocks"]
    finally:
        pf.shutdown()
        dc.shutdown()
    assert fleet_threads_alive() == 0


def test_warm_replica_refills_spilled_chains_from_source():
    """``fleet.warm_replica``: a joining replica adopts a peer's prefix
    chains — INCLUDING chains the peer evicted to its host tier (ISSUE
    18). The export's lookup takes the second-chance refill instead of
    re-running the prefill, and the warmed replica's first submit of a
    warmed prompt is an ordinary warm hit, bitwise the solo decode."""
    m = _model()
    fd = tempfile.mkdtemp(prefix="fleet_warm_")
    src_sched = DecodeScheduler(m, name="ws", host_blocks=32, **SCHED)
    tgt_sched = DecodeScheduler(m, name="wt", **SCHED)
    src = ReplicaAgent(src_sched, fleet_dir=fd, name="ws").start()
    tgt = ReplicaAgent(tgt_sched, fleet_dir=fd, name="wt").start()
    solo = DecodeScheduler(m, name="wsolo", **SCHED).start()
    try:
        ds, dt = wait_for_members(fd, ["ws", "wt"], timeout_s=20)
        rsrc = RemoteReplica(ds, fleet_dir=fd).start()
        rtgt = RemoteReplica(dt, fleet_dir=fd).start()
        rng = np.random.RandomState(21)
        prompts = [rng.randint(1, V, size=16).astype(np.int32)
                   for _ in range(4)]
        for p in prompts:
            rsrc.submit(p, max_new_tokens=8).result(timeout=120)
        # push every chain's leaf into the host tier, then wait for the
        # stager to land the spills (in-process agent: the scheduler is
        # THIS object) — the warm exports must find settled handles
        src_sched.prefix.evict(4)
        st = rsrc.stats()
        assert st["prefix"]["spills"] == 4 and \
            st["prefix"]["spilled_entries"] == 4
        deadline = time.time() + 10
        while time.time() < deadline:
            with src_sched.prefix._lock:
                pend = [h for h, _ in src_sched.prefix._spilled.values()
                        if h.state == SPILL_PENDING]
            if not pend:
                break
            time.sleep(0.01)
        assert not pend, "spill stage never settled"

        out = warm_replica(rsrc, rtgt, prompts, timeout_s=120)
        assert out["warmed"] == 4 and out["failed"] == 0, out
        st = rsrc.stats()
        assert st["prefix"]["hits_after_spill"] >= 1, \
            f"warm exports must refill, not recompute: {st['prefix']}"
        assert rtgt.stats()["prefix"]["entries"] > 0

        # the warmed replica serves the FIRST ask of a warmed prompt
        # as a warm hit, bitwise the solo decode
        want = solo.generate(prompts[0], 8)
        got = rtgt.submit(prompts[0], max_new_tokens=8).result(timeout=120)
        assert np.array_equal(want, got), \
            "warmed-replica tokens must be bitwise the solo decode"
        assert rtgt.stats()["prefix_hits"] >= 1, \
            "the warmed chain never produced a hit"
    finally:
        src.shutdown()
        tgt.shutdown()
        solo.shutdown()
    assert src_sched.stats()["host"]["host_blocks_in_use"] == 0, \
        "the source's host pool must drain at shutdown"
    assert fleet_threads_alive() == 0


def test_monitor_redials_torn_connection():
    """One torn connection must not remove a healthy, still-beating
    agent from the fleet forever: the FleetMonitor sees fresh beats
    behind a closed client and re-dials, so the drain/rejoin
    round-trips and later submits serve normally."""
    m = _model()
    fd = tempfile.mkdtemp(prefix="fleet_reconn_")
    ag = ReplicaAgent(DecodeScheduler(m, name="rc", **SCHED),
                      fleet_dir=fd, name="rc", beat_s=0.1).start()
    mon = None
    try:
        doc, = wait_for_members(fd, ["rc"], timeout_s=20)
        rep = RemoteReplica(doc, fleet_dir=fd).start()
        mon = FleetMonitor([rep], fleet_dir=fd, every_s=0.05,
                           stale_s=5.0).start()
        rng = np.random.RandomState(9)
        prompt = rng.randint(1, V, size=9).astype(np.int32)
        first = rep.submit(prompt, max_new_tokens=4).result(timeout=60)
        rep._client.close()          # torn connection; agent alive
        deadline = time.time() + 10
        while rep._client.closed and time.time() < deadline:
            time.sleep(0.05)
        assert not rep._client.closed, \
            "the monitor must re-dial a fresh member behind a torn conn"
        again = rep.submit(prompt, max_new_tokens=4).result(timeout=60)
        assert np.array_equal(first, again)
    finally:
        if mon is not None:
            mon.stop()
        ag.shutdown()
    assert fleet_threads_alive() == 0


def test_disaggregated_swap_covers_prefill_pool():
    """``DisaggregatedFleet.swap`` lands ONE version on BOTH pools.
    ``Router.swap`` alone leaves prefill specialists behind, and every
    later handoff is version-skew-refused (safe but useless — found
    driving the API end-to-end); after dis.swap the handoff ADOPTS and
    tokens are the new version's, bitwise the monolithic scheduler."""
    m = _model()
    fd = tempfile.mkdtemp(prefix="fleet_disswap_")
    pf = ReplicaAgent(DecodeScheduler(m, name="pf2", **SCHED),
                      fleet_dir=fd, name="pf2", role="prefill").start()
    dc = ReplicaAgent(DecodeScheduler(m, name="dc2", **SCHED),
                      fleet_dir=fd, name="dc2", role="decode").start()
    local = DecodeScheduler(m, name="mono2", **SCHED).start()
    try:
        dpf, ddc = wait_for_members(fd, ["pf2", "dc2"], timeout_s=20)
        rpf = RemoteReplica(dpf, fleet_dir=fd).start()
        rd0 = RemoteReplica(ddc, fleet_dir=fd)
        router = Router([rd0]).start()
        dis = DisaggregatedFleet(router, [rpf], [rd0])
        p2 = jax.tree_util.tree_map(lambda a: a * 1.01, m.params)
        v = dis.swap(p2)
        local.swap(p2, version=v)
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, V, size=37).astype(np.int32)
        want = local.generate(prompt, 8)
        got = dis.submit(prompt, max_new_tokens=8).result(timeout=120)
        assert np.array_equal(want, got), \
            "post-swap disaggregated tokens must be the new version's"
        st = dis.stats()
        assert st["handoffs"] == 1 and st["handoff_refused"] == 0, \
            f"the pool swap must keep handoffs landing: {st}"
        rpf.shutdown()
        router.shutdown()
    finally:
        pf.shutdown()
        dc.shutdown()
        local.shutdown()
    assert fleet_threads_alive() == 0


# -- cross-process: bitwise + fleet swap ------------------------------------

def test_remote_tokens_bitwise_and_fleet_swap_never_mixes(tmp_path):
    fd = str(tmp_path)
    m = _model()
    params_path = _save_params(m, fd)
    local = DecodeScheduler(m, name="oracle", **SCHED).start()
    proc = _spawn_agent(fd, "r0", params_path)
    try:
        docs = _members_or_skip(fd, ["r0"], [proc])
        rr = RemoteReplica(docs[0], fleet_dir=fd)
        router = Router([rr]).start()
        rng = np.random.RandomState(0)
        prompts = _prompts(rng, (5, 17, 26, 33))
        want = [local.generate(p, 12) for p in prompts]
        futs = [router.submit(p, max_new_tokens=12) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        for w, g in zip(want, got):
            assert np.array_equal(w, g), \
                "remote tokens must be bitwise the in-process replica's"
        assert all(f.version == "v0" for f in futs)

        # seeded sampling is (seed, position)-keyed: bitwise across the
        # process boundary too
        ws = local.generate(prompts[1], 10, temperature=0.7, top_p=0.9,
                            seed=11)
        gs = router.submit(prompts[1], max_new_tokens=10,
                           temperature=0.7, top_p=0.9,
                           seed=11).result(timeout=120)
        assert np.array_equal(ws, gs)

        # two-phase fleet swap over the wire: publish ships the tree,
        # activate flips — later admissions serve the new version and
        # answer with ITS tokens (no response mixes versions). The
        # second version has to be one whose tokens differ, or the swap
        # is invisible: * 1.01 moves no argmax of this model, * 1.5
        # moves every prompt's tokens
        p2 = jax.tree_util.tree_map(lambda a: a * 1.5, m.params)
        v2 = router.swap(p2)
        local.swap(p2, version=v2)
        futs2 = [router.submit(p, max_new_tokens=12) for p in prompts]
        got2 = [f.result(timeout=120) for f in futs2]
        want2 = [local.generate(p, 12) for p in prompts]
        for f, w, g in zip(futs2, want2, got2):
            assert f.version == v2
            assert np.array_equal(w, g), \
                "post-swap tokens must be the NEW version's, bitwise"
        assert not np.array_equal(want[0], want2[0]), \
            "the perturbed params must actually change tokens"

        # clean drain: the shutdown reply reports the remote ledger
        # empty (kv_blocks_in_use -> 0 in the agent process)
        meta, _ = rr._request("shutdown", {"drain": True}, timeout=120)
        assert meta["kv_blocks_in_use"] == 0
        router.shutdown()
    finally:
        _end([proc])
    assert _reap([proc]) == [0]
    local.shutdown()
    doc = read_member(fd, "r0")
    assert doc and doc.get("final") and not doc.get("dead")


# -- cross-process: agent death, KV-preserving failover ---------------------

@pytest.mark.slow  # ~23s of subprocess spawns; `make fleet-smoke`
# (tier-1) runs the same kill-mid-decode drill with exit-code asserts
# every run — this is the standalone, assert-rich version
def test_agent_death_mid_decode_zero_lost_partials_spliced(tmp_path):
    """Kill one replica process mid-decode (a PERMANENT chaos fault in
    its scheduler step — the deterministic process-death drill: the
    dying scheduler fails its in-flight typed-with-partial, the agent
    converts that into whole-process death). Every request completes on
    the survivor, recovered streams are BITWISE the uninterrupted run,
    and the dead process exits with the death code."""
    fd = str(tmp_path)
    m = _model()
    params_path = _save_params(m, fd)
    local = DecodeScheduler(m, name="oracle2", **SCHED).start()
    # r0 spawns with its death PRE-ARMED: a permanent fault at its 6th
    # decode-group dispatch — deterministically mid-decode for 24-token
    # generations (warmup drives the jit directly, not the chaos seam,
    # so only live traffic counts)
    procs = [_spawn_agent(fd, "r0", params_path, idx=1,
                          chaos={"sites": {"serving/scheduler_step": [
                              {"kind": "permanent", "nth": 6}]}}),
             _spawn_agent(fd, "r1", params_path, idx=2)]
    monitor = None
    try:
        docs = _members_or_skip(fd, ["r0", "r1"], procs)
        reps = [RemoteReplica(d, fleet_dir=fd) for d in docs]
        router = Router(reps, max_failovers=4).start()
        monitor = FleetMonitor(reps, fleet_dir=fd, every_s=0.1,
                               stale_s=10.0).start()
        rng = np.random.RandomState(1)
        prompts = _prompts(rng, (6, 9, 14, 21))
        want = [local.generate(p, 24) for p in prompts]
        futs = [router.submit(p, max_new_tokens=24) for p in prompts]
        got = [f.result(timeout=240) for f in futs]
        for w, g in zip(want, got):
            assert np.array_equal(w, g), \
                "recovered streams must be bitwise the uninterrupted run"
        st = router.stats()
        assert st["completed"] == len(prompts), f"lost requests: {st}"
        # the deadline-less round-robin put ~half the requests on r0;
        # its death at dispatch 6 left them mid-generation, so their
        # partials spliced through _recover_decode on r1
        assert st["kv_recoveries"] >= 1, st
        served = {f.trace["router"]["replica"] for f in futs}
        assert "r1" in served
        router.shutdown()
    finally:
        if monitor is not None:
            monitor.stop()
        _end(procs)
    codes = _reap(procs)
    assert codes == [86, 0], codes
    local.shutdown()


# -- cross-process: disaggregated prefill/decode ----------------------------

@pytest.mark.slow  # ~23s of subprocess spawns; `make fleet-smoke`
# (tier-1) asserts the handoff-bitwise gate against the monolithic
# oracle every run — this is the standalone greedy+sampled version
def test_prefill_decode_handoff_bitwise_greedy_and_sampled(tmp_path):
    """The ambitious end state: a prefill-specialist process runs the
    chunked prefill, its KV pages hand off in one framed binary hop,
    the decode-specialist adopts them (content-key-verified) and
    decodes — tokens BITWISE the monolithic single-process scheduler,
    greedy and seeded-sampled; the router's prefix affinity steers the
    request to the adopting replica."""
    fd = str(tmp_path)
    m = _model()
    params_path = _save_params(m, fd)
    local = DecodeScheduler(m, name="mono", **SCHED).start()
    procs = [_spawn_agent(fd, "pf", params_path, role="prefill", idx=1),
             _spawn_agent(fd, "d0", params_path, role="decode", idx=2)]
    try:
        dpf, dd0 = _members_or_skip(fd, ["pf", "d0"], procs)
        rpf = RemoteReplica(dpf, fleet_dir=fd)
        rd0 = RemoteReplica(dd0, fleet_dir=fd)
        router = Router([rd0]).start()
        rpf.start()
        dis = DisaggregatedFleet(router, [rpf], [rd0])
        rng = np.random.RandomState(2)
        long_prompts = _prompts(rng, (33, 40, 52))
        want = [local.generate(p, 10) for p in long_prompts]
        got = [dis.submit(p, max_new_tokens=10).result(timeout=240)
               for p in long_prompts]
        for w, g in zip(want, got):
            assert np.array_equal(w, g), \
                "disaggregated tokens must be bitwise the monolithic run"
        # seeded-sampled through the same handoff path
        ws = local.generate(long_prompts[0], 8, temperature=0.8,
                            top_p=0.85, seed=23)
        gs = dis.submit(long_prompts[0], max_new_tokens=8,
                        temperature=0.8, top_p=0.85,
                        seed=23).result(timeout=240)
        assert np.array_equal(ws, gs)
        st = dis.stats()
        assert st["handoffs"] == 4 and st["handoff_failed"] == 0, st
        # the decode specialist actually SKIPPED the handed-off prefill
        sd = rd0.stats()
        assert sd["prefix_hits"] >= 3
        assert sd["prefix_reused_tokens"] >= 3 * 32
        rpf.shutdown()
        router.shutdown()
    finally:
        _end(procs)
    assert _reap(procs) == [0, 0]
    local.shutdown()
