"""Host-only input-pipeline microbench (no device needed).

The realdata config's open question is `input_wait_frac 0.92` (an earlier
builder's on-chip run, 2026-07-31, older code) — the chip starved. That fraction conflates two
distinct failures: (a) the host pipeline cannot sustain the chip's
images/sec at all, or (b) it can, but the overlap/backpressure plumbing
stalls. This tool measures (a) in isolation: the C++ libjpeg prefetcher
(decode + RandomResizedCrop/hflip + normalize + bf16-NHWC batch build)
drained as fast as Python can iterate, no device in the loop.

Interpretation: if `images_per_sec` here >= the synthetic-headline
images/sec, the realdata gap is (b) — fix the overlap; if it is far
below, the pipeline needs more workers / faster decode, and
`images_per_sec / workers` says whether scaling is linear.

Runs anywhere (CPU-only box included; the run on the TPU host is the
number that matters — its core count feeds the decode workers). One JSON
line on stdout, in bench.py's line schema.

Usage: python tools/bench_input_pipeline.py [--batch 256] [--size 224]
           [--workers N] [--batches 30]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--jpeg-size", type=int, default=256)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--batches", type=int, default=30)
    ap.add_argument("--n-images", type=int, default=None)
    args = ap.parse_args()

    import numpy as np
    from bigdl_tpu.native import JpegFolderPrefetcher
    import bench

    n_images = args.n_images or args.batch * 8
    paths, labels = bench._ensure_jpeg_folder(n_images, args.jpeg_size)
    # the SAME worker policy as the realdata bench — the roofline must
    # be measured at the configuration it calibrates
    workers = args.workers or bench._default_jpeg_workers()
    queue_capacity = 4

    pf = JpegFolderPrefetcher(
        paths, labels, args.size, args.size,
        mean=(124.0, 117.0, 104.0), std=(59.0, 57.0, 57.0),
        batch_size=args.batch, n_workers=workers,
        queue_capacity=queue_capacity, out="bf16_nhwc", augment=True)

    it = pf.data(train=True, loop_epochs=10_000)
    t0 = time.perf_counter()
    mb = next(it)
    first = time.perf_counter() - t0          # queue-fill latency
    assert np.asarray(mb.input).shape == (args.batch, args.size,
                                          args.size, 3)
    # steady state: the backlog built during first-batch wait (queue +
    # one in-flight batch per worker) arrives for free — drain PAST it
    # before timing or small --batches counts inflate the roofline
    warm = max(args.batches // 10, queue_capacity + workers + 1)
    for _ in range(warm):
        next(it)
    t0 = time.perf_counter()
    for _ in range(args.batches):
        next(it)
    dt = time.perf_counter() - t0
    v = args.batch * args.batches / dt
    print(json.dumps({
        "metric": "input_pipeline_images_per_sec",
        "value": round(v, 1),
        "unit": "images/sec (host only)",
        "vs_baseline": None,
        "batch": args.batch, "size": args.size, "workers": workers,
        "host_cores": os.cpu_count(),
        "first_batch_s": round(first, 2),
        "per_worker_images_per_sec": round(v / workers, 1),
    }))


if __name__ == "__main__":
    main()
