#!/usr/bin/env python3
"""Compile every hand-written Pallas kernel once on the chip and report,
verbatim, what the compiler accepts and what it refuses.

Run it through the chip tool (``chiprun -- python tools/kernel_probe.py``).
It is a probe, not a gate on the serving path: each case compiles and runs
on its own, a refusal is printed with the compiler's own message and the
next case still runs, and the exit code is 1 when anything was refused.
``chip_smoke.py`` is the pass/fail check of the default path; this tool
answers "which shapes does Mosaic take" for the kernels behind
``ResNet(fused="pallas")`` and for paged-attention block sizes other than
the shipped one.

Cases:
  * flash attention forward and gradients at the trainer's shape;
  * paged decode attention at S=1 and at prefill-chunk shapes, for block
    sizes 16..128;
  * the three ResNet families (fused_matmul, fused_chain, fused_conv),
    forward and gradients, at the four ResNet-50 stage shapes.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

RESULTS = []


def case(name, build):
    """``build()`` returns (fn, args); the case jit-compiles ``fn`` and
    runs it once. Records ok / the compiler's message."""
    t0 = time.perf_counter()
    try:
        fn, args = build()
        if fn is None:
            RESULTS.append({"case": name, "ok": None,
                            "note": "wrapper returned None (no block fits "
                                    "its VMEM model; caller uses XLA)"})
            print(f"[skip] {name}: wrapper declined the shape", flush=True)
            return
        compiled = jax.jit(fn).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        out = compiled(*args)
        jax.block_until_ready(out)
        finite = all(bool(jnp.isfinite(l.astype(jnp.float32)).all())
                     for l in jax.tree_util.tree_leaves(out))
        RESULTS.append({"case": name, "ok": True, "finite": finite,
                        "compile_s": round(t_compile, 2)})
        print(f"[ ok ] {name}: compiled in {t_compile:.1f}s, "
              f"finite={finite}", flush=True)
    except Exception as e:  # noqa: BLE001 — the probe's job is to record it
        msg = f"{type(e).__name__}: {e}"
        RESULTS.append({"case": name, "ok": False, "error": msg[:4000]})
        print(f"[FAIL] {name}: {msg[:1500]}", flush=True)
        traceback.print_exc(limit=3, file=sys.stderr)


def _rand(shape, dtype, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32), dtype)


def flash_cases():
    from bigdl_tpu.kernels.flash_attention import flash_attention_fused
    for dt in (jnp.float32, jnp.bfloat16):
        for T in (1024, 200):
            def build(dt=dt, T=T):
                q, k, v = (_rand((2, 16, T, 64), dt, s) for s in range(3))
                return (lambda q, k, v: flash_attention_fused(
                    q, k, v, causal=True)), (q, k, v)
            case(f"flash_fwd T={T} {jnp.dtype(dt).name}", build)

            def build_g(dt=dt, T=T):
                q, k, v = (_rand((2, 16, T, 64), dt, s) for s in range(3))
                f = lambda q, k, v: flash_attention_fused(  # noqa: E731
                    q, k, v, causal=True).astype(jnp.float32).sum()
                return jax.grad(f, argnums=(0, 1, 2)), (q, k, v)
            case(f"flash_bwd T={T} {jnp.dtype(dt).name}", build_g)


def paged_cases():
    from bigdl_tpu.kernels.paged_attention import paged_decode_attention
    nH, D, max_seq = 16, 64, 1280
    for bs in (16, 32, 64, 128):
        nblk = max_seq // bs
        for B, S in ((8, 1), (2, 1), (1, 256), (1, 2)):
            def build(bs=bs, nblk=nblk, B=B, S=S):
                pool = B * nblk + 1
                kp = _rand((pool, nH, bs, D), jnp.float32, 1)
                vp = _rand((pool, nH, bs, D), jnp.float32, 2)
                q = _rand((B, nH, S, D), jnp.float32, 3)
                tbl = jnp.asarray(
                    1 + np.arange(B * nblk).reshape(B, nblk), jnp.int32)
                pos = jnp.asarray(np.linspace(5, max_seq - S - 1, B),
                                  jnp.int32)
                return (lambda q, kp, vp, tbl, pos: paged_decode_attention(
                    q, kp, vp, tbl, pos)), (q, kp, vp, tbl, pos)
            case(f"paged bs={bs} B={B} S={S} f32", build)


def _declined(wrapper, *args):
    """True when the wrapper returns None for these shapes (its own VMEM
    model found no block) — traced abstractly, nothing compiles."""
    return jax.eval_shape(wrapper, *args) is None


# (H=W, Cin of the block, Cmid, Cout) for ResNet-50 stages 0..3
_STAGES = ((56, 256, 64, 256), (28, 512, 128, 512),
           (14, 1024, 256, 1024), (7, 2048, 512, 2048))


def resnet_cases(batch=64):
    from bigdl_tpu.kernels.fused_chain import fused_residual_matmul_nhwc
    from bigdl_tpu.kernels.fused_conv import fused_bn_relu_conv3x3
    from bigdl_tpu.kernels.fused_matmul import (fused_bn_relu_matmul,
                                                fused_bn_relu_matmul_nhwc)
    dt = jnp.bfloat16
    for hw, cin, cmid, cout in _STAGES:
        tag = f"stage{hw}x{hw} B={batch}"
        x = _rand((batch, hw, hw, cin), dt, 0)
        w1 = _rand((cin, cmid), dt, 1) * 0.05
        a = jnp.ones((cin,), dt)
        b = jnp.zeros((cin,), dt)

        def nhwc_fwd(x=x, w1=w1, a=a, b=b):
            if _declined(fused_bn_relu_matmul_nhwc, x, w1, a, b):
                return None, None
            return (lambda x, w, a, b: fused_bn_relu_matmul_nhwc(
                x, w, a, b)), (x, w1, a, b)
        case(f"fused_matmul_nhwc fwd {tag} K={cin} N={cmid}", nhwc_fwd)

        def nhwc_bwd(x=x, w1=w1, a=a, b=b):
            if _declined(fused_bn_relu_matmul_nhwc, x, w1, a, b):
                return None, None

            def loss(x, w, a, b):
                z, s1, s2 = fused_bn_relu_matmul_nhwc(x, w, a, b)
                return z.astype(jnp.float32).sum() + s1.sum() + s2.sum()
            return jax.grad(loss, argnums=(0, 1, 2, 3)), (x, w1, a, b)
        case(f"fused_matmul_nhwc bwd {tag} K={cin} N={cmid}", nhwc_bwd)

        def flat_bwd(x=x, w1=w1, a=a, b=b):
            xf = x.reshape(-1, x.shape[-1])

            def loss(x, w, a, b):
                z, s1, s2 = fused_bn_relu_matmul(x, w, a, b)
                return z.astype(jnp.float32).sum() + s1.sum() + s2.sum()
            return jax.grad(loss, argnums=(0, 1, 2, 3)), (xf, w1, a, b)
        case(f"fused_matmul flat fwd+bwd {tag} K={cin} N={cmid}", flat_bwd)

        r = _rand((batch, hw, hw, cout), dt, 2)
        z = _rand((batch, hw, hw, cout), dt, 3)
        wn = _rand((cout, cmid), dt, 4) * 0.05
        a3 = jnp.ones((cout,), dt)
        b3 = jnp.zeros((cout,), dt)

        def chain_bwd(z=z, r=r, wn=wn, a3=a3, b3=b3):
            if _declined(fused_residual_matmul_nhwc, z, r, wn, a3, b3):
                return None, None

            def loss(z, r, w, a, b):
                h, zo, s1, s2 = fused_residual_matmul_nhwc(z, r, w, a, b)
                return (h.astype(jnp.float32).sum()
                        + zo.astype(jnp.float32).sum() + s1.sum() + s2.sum())
            return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), \
                (z, r, wn, a3, b3)
        case(f"fused_chain fwd+bwd {tag} K={cout} N={cmid}", chain_bwd)

        xm = _rand((batch, hw, hw, cmid), dt, 5)
        w2 = _rand((3, 3, cmid, cmid), dt, 6) * 0.05
        am = jnp.ones((cmid,), dt)
        bm = jnp.zeros((cmid,), dt)

        def conv_bwd(xm=xm, w2=w2, am=am, bm=bm):
            if _declined(fused_bn_relu_conv3x3, xm, w2, am, bm):
                return None, None

            def loss(x, w, a, b):
                z, s1, s2 = fused_bn_relu_conv3x3(x, w, a, b)
                return z.astype(jnp.float32).sum() + s1.sum() + s2.sum()
            return jax.grad(loss, argnums=(0, 1, 2, 3)), (xm, w2, am, bm)
        case(f"fused_conv3x3 fwd+bwd {tag} K=N={cmid}", conv_bwd)


def main():
    dev = jax.devices()[0]
    print(f"kernel_probe: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"kernel_probe: platform is {dev.platform!r}, not "
                         "'tpu' — the probe reports the TPU compiler's "
                         "answers and has nothing to say elsewhere")
    which = sys.argv[1:] or ["flash", "paged", "resnet"]
    if "flash" in which:
        flash_cases()
    if "paged" in which:
        paged_cases()
    if "resnet" in which:
        resnet_cases()
    out = os.path.join(_REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kernel_probe.json"), "w") as f:
        json.dump({"device_kind": dev.device_kind, "jax": jax.__version__,
                   "results": RESULTS}, f, indent=1)
    bad = [r for r in RESULTS if r["ok"] is False]
    print(f"kernel_probe: {len(RESULTS)} cases, {len(bad)} refused",
          flush=True)
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
