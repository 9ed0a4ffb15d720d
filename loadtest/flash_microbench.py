"""Microbenchmark the pallas flash attention kernels on the attached
chip — the profile-first follow-up to VERDICT r3 item 6: at hd=64 the
fwd kernel measures ~0.32 of peak and the bwd ~0.29, and together they
are ~50% of the 1B@16k step. This driver times fwd / bwd in isolation
(scan-amortized, like bench.py's op compare) so kernel changes can be
evaluated in seconds instead of full-step minutes.

    python -m loadtest.flash_microbench --seq 16384 --heads 32 --kv 8 --hd 64
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
from jax import lax


def timed(fn, *args, iters=2, scan_n=8):
    """Best time per call, ``scan_n`` calls per dispatch (the host's
    per-call dispatch is not charged to each of them)."""
    def scanned(*a):
        def body(c, _):
            o = fn(c, *a[1:])
            o0 = o[0] if isinstance(o, tuple) else o
            return c * 0.999 + o0.astype(a[0].dtype) * 1e-3, None
        return lax.scan(body, a[0], None, length=scan_n)[0]

    jf = jax.jit(scanned)
    float(jf(*args).sum())  # compile + warm
    best = None
    for _ in range(iters):
        t0 = time.time()
        float(jf(*args).sum())
        dt = (time.time() - t0) / scan_n
        best = dt if best is None else min(best, dt)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv", type=int, default=8)
    ap.add_argument("--hd", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--block-q", type=int, default=None)
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument("--bwd", action="store_true", help="time backward too")
    ap.add_argument("--raw", action="store_true",
                    help="time the head-major kernel alone (no transposes)")
    args = ap.parse_args()

    from odh_kubeflow_tpu.ops.pallas_attention import flash_attention
    from odh_kubeflow_tpu.utils.tpu import peak_flops_per_chip

    peak = peak_flops_per_chip(jax.devices()[0])
    B, Hq, Hkv, S, hd = args.batch, args.heads, args.kv, args.seq, args.hd
    key = jax.random.PRNGKey(0)
    kq, kk, kv2 = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, Hq, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(kv2, (B, S, Hkv, hd), jnp.bfloat16)

    kw = {}
    if args.block_q:
        kw["block_q"] = args.block_q
    if args.block_k:
        kw["block_k"] = args.block_k
    if args.raw:
        # head-major inputs straight into the grid wrapper: isolates
        # the kernel from the [B,S,H,hd]→[B,H,S,hd] transposes (which
        # the profile shows cost ~as much as the kernel at hd=64)
        from odh_kubeflow_tpu.ops import pallas_attention as pa

        qm = jnp.swapaxes(q, 1, 2)
        km = jnp.swapaxes(k, 1, 2)
        vm = jnp.swapaxes(v, 1, 2)

        def raw_fwd(qm, km, vm):
            return pa._fwd(
                qm, km, vm, None, None,
                scale=hd ** -0.5, causal=True, q_offset=0, sk=S,
                block_q=kw.get("block_q", pa.DEFAULT_BLOCK_Q),
                block_k=kw.get("block_k", pa.DEFAULT_BLOCK_K),
                interpret=False,
            )[0]

        pairs = S * (S + 1) / 2
        fwd_flops = 4 * B * Hq * pairs * hd
        dt = timed(raw_fwd, qm, km, vm)
        out = {"shape": f"B{B} Hq{Hq} Hkv{Hkv} S{S} hd{hd}", **kw,
               "raw_fwd_ms": round(dt * 1e3, 2),
               "raw_fwd_eff": round(fwd_flops / dt / peak, 4)}
        print(json.dumps(out))
        return
    fwd = functools.partial(flash_attention, causal=True, **kw)

    # causal pair count: S(S+1)/2 per head
    pairs = S * (S + 1) / 2
    fwd_flops = 4 * B * Hq * pairs * hd
    out = {"shape": f"B{B} Hq{Hq} Hkv{Hkv} S{S} hd{hd}", **kw}

    dt = timed(fwd, q, k, v)
    out["fwd_ms"] = round(dt * 1e3, 2)
    out["fwd_eff"] = round(fwd_flops / dt / peak, 4)

    if args.bwd:
        def loss(q, k, v):
            return (flash_attention(q, k, v, causal=True, **kw)
                    .astype(jnp.float32).sum())

        grads = jax.grad(loss, argnums=(0, 1, 2))

        def gq(q, k, v):
            # combine all three cotangents so the dkv kernel cannot be
            # DCE'd out of the measurement
            dq, dk, dv = grads(q, k, v)
            return dq + (dk + dv).repeat(q.shape[2] // k.shape[2], axis=2)

        dt = timed(gq, q, k, v)
        # fwd recompute inside grad: jax.grad of the custom_vjp runs
        # fwd (returns residuals) + bwd; time reported is the full pair
        bwd_flops = fwd_flops * 2.5
        out["fwdbwd_ms"] = round(dt * 1e3, 2)
        out["fwdbwd_eff"] = round((fwd_flops + bwd_flops) / dt / peak, 4)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
