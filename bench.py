"""Headline benchmark: ResNet-50 ImageNet-shape sync-SGD images/sec/chip.

Matches BASELINE.json: "images/sec/chip ResNet-50 sync-SGD". The fixed
baseline constant is the reference's MKL-DNN Xeon-node throughput estimate
(~60 img/s fp32 per node for ResNet-50 training, the deployment the reference
README benchmarks against); ``vs_baseline`` = our images/sec/chip / 60.

One process, one chip: ``python bench.py`` measures on the TPU or exits
non-zero saying which platform it found — there is no retry, no cached
line and no CPU stand-in. ``python bench.py --smoke`` is the CPU plumbing
check (tiny shapes, pinned to the CPU platform); every line it prints says
``"backend": "cpu"``. A config that raises prints a ``*_failed`` line, the
remaining configs still run, and the exit code is non-zero.

Secondary configs (BASELINE.json): ``python bench.py --all`` additionally
benchmarks LeNet-5/MNIST, VGG-16/CIFAR-10, LSTM/PTB and int8 Inception-v1 —
one JSON line each, after the headline line. ``--config KEY`` (repeatable)
runs only the named ones (``headline`` or a ``bench_extra.CONFIGS`` key).

TPU-first choices in the benchmark itself: NHWC activations (TPU-native conv
layout), bf16 compute with f32 master params (MXU-friendly; SGD update in
f32), input bound on device, donated buffers. MFU is computed from XLA's own
compiled cost analysis when available (falling back to the analytic
2*4.09 GMAC * 3 per image) against the chip's bf16 peak.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BASELINE_IMG_PER_SEC = 60.0  # MKL-DNN Xeon node, ResNet-50 train (SURVEY §6)


def _peak_flops(device_kind: str) -> float:
    """Chip peak FLOP/s — the SAME table the runtime's live ``perf/mfu``
    gauge uses (``observability/perf.py``), so the offline bench MFU and
    the live gauge can never disagree about the hardware ceiling. An
    unknown device raises; callers compute MFU on the TPU only."""
    from bigdl_tpu.observability.perf import peak_flops
    return peak_flops(device_kind)


def _mfu(on_tpu: bool, flops_per_sec: float):
    """Utilization against the chip's peak; None off the TPU (no peak is
    assumed for a device the table does not know)."""
    if not on_tpu:
        return None
    import jax
    return round(flops_per_sec / _peak_flops(jax.devices()[0].device_kind),
                 4)


def resnet_bench_variant():
    """Resolve the (fused, pool_grad) ResNet variant from the BENCH_* env —
    the ONE parser shared by the bench and tools/profile_resnet.py so the
    profiler always captures the variant the bench actually runs. Unknown
    values raise: they must not silently benchmark the wrong arm."""
    fused_env = os.environ.get("BENCH_FUSED", "xla")
    try:
        fused = {"1": "pallas", "pallas": "pallas", "xla": "xla",
                 "0": "none", "none": "none"}[fused_env]
    except KeyError:
        raise SystemExit(f"BENCH_FUSED={fused_env!r}: expected "
                         "xla | pallas/1 | none/0")
    pool_grad = os.environ.get("BENCH_POOL_GRAD", "exact")
    if pool_grad not in ("exact", "fast"):
        raise SystemExit(f"BENCH_POOL_GRAD={pool_grad!r}: expected "
                         "exact | fast")
    stem = os.environ.get("BENCH_STEM", "conv7")
    if stem not in ("conv7", "s2d"):
        raise SystemExit(f"BENCH_STEM={stem!r}: expected conv7 | s2d")
    return fused, pool_grad, stem


def _build_resnet_step(batch, size, superstep: int = 1):
    """Compile the ResNet-50 train step (fwd + CE loss + bwd + momentum
    SGD, donated buffers). Returns (step, carry, lr, flops_per_step) —
    shared by the synthetic headline and the real-data config.

    ``superstep > 1`` compiles K fused steps as one ``lax.scan`` program
    over ``[K, batch, ...]`` stacks (the optimizer's superstep mode, in
    bench form): one dispatch and one loss readback per K steps;
    ``flops_per_step`` then reports the whole K-step program."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.utils import engine

    from bigdl_tpu.utils.amp import bf16_params
    engine.set_seed(0)
    # NHWC: TPU-native conv layout (channels-last); f32 master params,
    # bf16 compute inside the step (MXU path), f32 SGD update.
    # BENCH_FUSED selects the bottleneck variant (models/resnet.py):
    #   xla (default) — layout-preserving 1x1-conv-as-dot restructure with
    #     affine prologue + one-pass stats epilogue, fused by XLA; the
    #     round-3 on-chip A/B measured it +4.2% over plain lax.conv
    #     (2441 vs 2342 img/s). The flattened-reshape form of the same
    #     math was 1.75x SLOWER — layout preservation is the whole win.
    #   1 — the hand-written Pallas fused kernel arm (kernels/fused_matmul)
    #   0 — plain unfused bottlenecks (the pre-round-3 baseline)
    fused, pool_grad, stem = resnet_bench_variant()
    # BENCH_POOL_GRAD=fast enables the scatter-free maxpool backward
    # (nn/pool.py; measured -15% on v5e, kept as an option)
    model = ResNet(class_num=1000, depth=50, format="NHWC", fused=fused,
                   pool_grad=pool_grad, stem=stem)
    params, mstate = model.init(jax.random.PRNGKey(0))
    crit = CrossEntropyCriterion()
    optim = SGD(learningrate=0.1, momentum=0.9)
    opt_state = optim.init_state(params)

    def train_step(params, opt_state, mstate, x, y, lr):
        def loss_fn(p):
            p16 = bf16_params(p)
            out, new_state = model.apply(p16, mstate, x, training=True,
                                         rng=jax.random.PRNGKey(0))
            return crit._forward(out.astype(jnp.float32), y), new_state
        (loss, new_mstate), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt = optim.update(grads, params, opt_state, lr)
        return loss, new_params, new_opt, new_mstate

    def train_superstep(params, opt_state, mstate, xs, ys, lr):
        def body(carry, inp):
            p, o, m = carry
            bx, by = inp
            loss, p, o, m = train_step(p, o, m, bx, by, lr)
            return (p, o, m), loss
        (params, opt_state, mstate), losses = jax.lax.scan(
            body, (params, opt_state, mstate), (xs, ys))
        return losses, params, opt_state, mstate

    if superstep > 1:
        x = jnp.zeros((superstep, batch, size, size, 3), jnp.bfloat16)
        y = jnp.zeros((superstep, batch), jnp.int32)
        fn = train_superstep
    else:
        x = jnp.zeros((batch, size, size, 3), jnp.bfloat16)
        y = jnp.zeros((batch,), jnp.int32)
        fn = train_step
    lr = jnp.float32(0.1)
    # AOT-compile once and reuse the executable for the timed loop (a plain
    # jit call after .lower().compile() would trace+compile a second time).
    step = jax.jit(fn, donate_argnums=(0, 1, 2)) \
              .lower(params, opt_state, mstate, x, y, lr).compile()

    flops_per_step = None
    try:
        ca = step.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops_per_step = float(ca.get("flops", 0.0)) or None
    except Exception:
        pass
    if not flops_per_step:
        # analytic fallback: 4.09 GMAC fwd/image * 2 flops/MAC * 3 (train)
        flops_per_step = (2 * 4.089e9 * 3 * batch * (size / 224.0) ** 2
                          * max(1, superstep))
    return step, [params, opt_state, mstate], lr, flops_per_step


def bench_resnet50():
    import jax
    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    # env overrides make on-chip batch/step sweeps cheap (BENCH_*)
    batch = int(os.environ.get("BENCH_BATCH", 256 if on_tpu else 4))
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 2))
    warmup = int(os.environ.get("BENCH_WARMUP", 3 if on_tpu else 1))
    # BENCH_SUPERSTEP=K fuses K steps per dispatch (lax.scan) — the K
    # sweep companion of the optimizer's set_superstep mode
    superstep = max(1, int(os.environ.get("BENCH_SUPERSTEP", "1")))
    size = 224 if on_tpu else 64

    step, carry, lr, flops_per_step = _build_resnet_step(batch, size,
                                                         superstep)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, size, size, 3).astype(np.float32),
                    jnp.bfloat16)
    y = jnp.asarray(rng.randint(1, 1001, size=(batch,)).astype(np.int32))
    if superstep > 1:
        x = jnp.stack([x] * superstep)
        y = jnp.stack([y] * superstep)
    dispatches = max(1, steps // superstep)

    for _ in range(warmup):
        loss, *carry = step(*carry, x, y, lr)
    # full sync by host read; under a superstep the loss is a [K] vector —
    # still ONE readback
    final = np.asarray(loss)
    t0 = time.perf_counter()
    for _ in range(dispatches):
        loss, *carry = step(*carry, x, y, lr)
    final = np.asarray(loss)  # forces the whole chained step sequence
    dt = time.perf_counter() - t0
    assert np.isfinite(final).all()
    img_per_sec = batch * superstep * dispatches / dt

    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "mfu": _mfu(on_tpu, flops_per_step * dispatches / dt),
        "superstep_k": superstep,
        "dispatches": dispatches,
        "backend": backend,
        "device": jax.devices()[0].device_kind,
    }


_JPEG_DIR = os.environ.get("BENCH_JPEG_DIR", "/tmp/bigdl_tpu_bench_jpegs")


def _ensure_jpeg_folder(n_images: int, jpeg_size: int):
    """Create (once) a folder of real JPEGs via the native libjpeg encoder:
    smooth random blobs + noise so files have photo-like entropy, 1000
    synthetic classes in the filename."""
    import numpy as np
    from bigdl_tpu.native import encode_jpeg

    # per-config subfolder: different (count, size) configs must never
    # validate against each other's files
    cfg_dir = os.path.join(_JPEG_DIR, f"{n_images}x{jpeg_size}")
    tag = os.path.join(cfg_dir, ".complete")
    if os.path.exists(tag):
        paths = sorted(
            os.path.join(cfg_dir, f) for f in os.listdir(cfg_dir)
            if f.endswith(".jpg"))
        if len(paths) >= n_images:
            labels = [int(os.path.basename(p).split("_")[0])
                      for p in paths[:n_images]]
            return paths[:n_images], labels
    os.makedirs(cfg_dir, exist_ok=True)
    rng = np.random.RandomState(0)
    yy, xx = np.mgrid[0:jpeg_size, 0:jpeg_size].astype(np.float32)
    paths, labels = [], []
    for i in range(n_images):
        label = int(rng.randint(1, 1001))
        fx, fy, ph = rng.rand(3, 3) * 0.1, rng.rand(3, 3) * 0.1, \
            rng.rand(3, 3) * 6.28
        img = np.zeros((jpeg_size, jpeg_size, 3), np.float32)
        for c in range(3):
            for k in range(3):
                img[:, :, c] += np.sin(fx[c, k] * xx + fy[c, k] * yy
                                       + ph[c, k])
        img = (img - img.min()) / (np.ptp(img) + 1e-6) * 235.0
        img += rng.randn(jpeg_size, jpeg_size, 3) * 10.0
        img = np.clip(img, 0, 255).astype(np.uint8)
        p = os.path.join(cfg_dir, f"{label}_{i:05d}.jpg")
        with open(p, "wb") as f:
            f.write(encode_jpeg(img, quality=90))
        paths.append(p)
        labels.append(label)
    with open(tag, "w") as f:
        f.write("ok")
    return paths, labels


def _default_jpeg_workers() -> int:
    """Decode workers (shared by the realdata bench and
    tools/bench_input_pipeline.py so the roofline and the training run
    are measured at the SAME worker count): at least 4 (a few decode
    threads hide each other's I/O stalls even on one core), scaling to
    the host's cores up to 16. BENCH_JPEG_WORKERS overrides."""
    return int(os.environ.get("BENCH_JPEG_WORKERS",
                              min(16, max(4, os.cpu_count() or 1))))


def bench_resnet50_realdata():
    """ResNet-50 train fed by the C++ libjpeg prefetcher over a folder of
    REAL JPEG files (decode + bilinear resize + normalize on host worker
    threads), with double-buffered host→device transfer: the next batch is
    fetched and device_put while the chip runs the current step (the
    reference's executor-side ImageNet pipeline, TrainImageNet.scala).
    Reports images/sec plus the fraction of wall time the host spent
    blocked on the input pipeline (input_wait_frac ~0 ⇒ compute-bound)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu.native import JpegFolderPrefetcher

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    batch = int(os.environ.get("BENCH_BATCH", 256 if on_tpu else 4))
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 2))
    warmup = int(os.environ.get("BENCH_WARMUP", 3 if on_tpu else 1))
    size = 224 if on_tpu else 64
    n_images = batch * 8 if on_tpu else batch * 4
    jpeg_size = 256 if on_tpu else 96

    paths, labels = _ensure_jpeg_folder(n_images, jpeg_size)
    # each worker holds one fully-built batch (~154 MB at B256/224²) while
    # blocked on the bounded queue, so the default is capped: memory is
    # workers × batch_bytes beyond the queue itself
    n_workers = _default_jpeg_workers()
    # bf16_nhwc: decode workers emit accelerator-ready batches — no host
    # f32→bf16 cast (measured 0.24 s/batch), no device-side transpose,
    # half the host→device bytes
    # augment=True: the realdata config trains with the reference's real
    # ImageNet transform (RandomResizedCrop + hflip) on the decode workers
    # stage_to_device: the decode workers' output buffer (reusable host
    # staging ring) hands straight to device_put — no per-batch numpy
    # allocation or copy between libjpeg and the chip
    pf = JpegFolderPrefetcher(
        paths, labels, size, size, mean=(124.0, 117.0, 104.0),
        std=(59.0, 57.0, 57.0), batch_size=batch, n_workers=n_workers,
        queue_capacity=4, out="bf16_nhwc", augment=True,
        stage_to_device=True)

    step, carry, lr, flops_per_step = _build_resnet_step(batch, size)

    def batches():
        """Endless stream of device-resident (x, y). loop_epochs keeps the
        decode workers running across epoch boundaries (a cold restart
        refills the whole queue: 7-11 s stall on a 1-core host); batches
        arrive bf16 NHWC as DEVICE arrays (the prefetcher's staging ring
        already device_put them) — only the label cast remains."""
        while True:
            for mb in pf.data(train=True, loop_epochs=1000):
                yield mb.input, jnp.asarray(mb.target, jnp.int32)

    def pull(it, wait):
        """next(it) is where the host blocks on the input pipeline."""
        t0 = time.perf_counter()
        out = next(it)
        wait[0] += time.perf_counter() - t0
        return out

    wait = [0.0]
    it = batches()
    nxt = pull(it, wait)
    for _ in range(warmup):
        x, y = nxt
        loss, *carry = step(*carry, x, y, lr)
        nxt = pull(it, wait)
    float(loss)
    wait[0] = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        x, y = nxt
        loss, *carry = step(*carry, x, y, lr)   # async dispatch
        nxt = pull(it, wait)                    # overlaps the device step
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss)
    img_per_sec = batch * steps / dt
    return {
        "metric": "realdata_resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "mfu": _mfu(on_tpu, flops_per_step * steps / dt),
        "input_wait_frac": round(wait[0] / dt, 4),
        # input_wait_frac ≈ 1 means decode-bound: single-core libjpeg
        # decode+resize runs ~230 img/s/core, so feeding the chip's
        # synthetic rate needs ~ (synthetic/230) host cores. host_cpus
        # makes that legible in the recorded line.
        "host_cpus": os.cpu_count(),
        "jpeg_workers": n_workers,
        "backend": backend,
        "device": jax.devices()[0].device_kind,
    }


def _run_config(which: str):
    """Run ONE config in this process: ``headline`` or a
    ``bench_extra.CONFIGS`` key. Exceptions propagate to :func:`main`."""
    from bigdl_tpu import observability as obs
    if which == "headline":
        with obs.span("bench/headline"):
            return bench_resnet50()
    from bench_extra import bench_one
    return bench_one(which)


def _write_metrics_dump(all_lines):
    """Mirror the final bench lines through the observability registry
    and write the BENCH_*-compatible metrics dump — bench results and
    runtime metrics share one {"metric", "value", "unit"} schema.
    Opt out with BENCH_METRICS_OUT=''."""
    out = os.environ.get("BENCH_METRICS_OUT", "BENCH_METRICS.json")
    if not out or not all_lines:
        return
    if not os.path.isabs(out):
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)), out)
    try:
        from bigdl_tpu import observability as obs
        reg = obs.MetricsRegistry()
        for line in all_lines:
            obs.record_bench_line(line, reg)
        obs.write_metrics_dump(out, reg)
    except Exception as e:  # the dump must never fail the bench itself
        print(f"bench: metrics dump failed: {e}", file=sys.stderr)


def main(argv=None):
    from bench_extra import CONFIGS
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--all", action="store_true",
                    help="headline plus every secondary config")
    ap.add_argument("--config", action="append", default=[],
                    choices=["headline", *CONFIGS],
                    help="run only this config (repeatable)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU plumbing check at tiny shapes")
    args = ap.parse_args(argv)

    import jax
    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
        # the auto policy's first arm is remat=0, so without the pin the
        # LM configs' remat=True paths would lose their plumbing check
        os.environ.setdefault("BENCH_LM_REMAT", "1")
    dev = jax.devices()[0]
    if not args.smoke and dev.platform != "tpu":
        raise SystemExit(
            f"bench.py: platform is {dev.platform!r} ({dev.device_kind}), "
            "not 'tpu' — it measures on the chip or not at all; "
            "`python bench.py --smoke` is the CPU plumbing check")

    from bigdl_tpu import observability as obs
    from bigdl_tpu.utils import engine
    engine.maybe_enable_compilation_cache()
    # observability ON: the jax compilation-cache monitoring events only
    # bridge into the engine/compile_cache_hits|misses counters while
    # enabled, and those counters ride every result line
    obs.enable()
    reg = obs.registry()

    configs = args.config or (["headline", *CONFIGS] if args.all
                              else ["headline"])
    all_lines, failed = [], []
    for which in configs:
        try:
            line = _run_config(which)
        except Exception as e:  # noqa: BLE001 — report, run the rest, exit 1
            traceback.print_exc()
            failed.append(which)
            line = {"metric": f"{which}_failed", "value": 0, "unit": "error",
                    "vs_baseline": 0, "backend": dev.platform,
                    "error": f"{type(e).__name__}: {e}"[-300:]}
        line.setdefault("compile_cache_hits",
                        int(reg.counter("engine/compile_cache_hits").value))
        line.setdefault("compile_cache_misses",
                        int(reg.counter("engine/compile_cache_misses").value))
        print(json.dumps(line), flush=True)
        all_lines.append(line)
    # bench/* spans are exportable with
    # BIGDL_TPU_TRACE=1 BENCH_TRACE_OUT=/path/trace.json
    trace_out = os.environ.get("BENCH_TRACE_OUT")
    if trace_out:
        obs.write_chrome_trace(trace_out)
    _write_metrics_dump(all_lines)
    if failed:
        raise SystemExit(f"bench.py: {len(failed)} config(s) failed: "
                         f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
