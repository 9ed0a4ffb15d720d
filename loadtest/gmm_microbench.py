"""Grouped-matmul kernel-B microbench + block-size sweep (VERDICT r4
item 2 / r5 item 4).

Round 4 profiled the 8×1B MoE step and found kernel A (rhs-resident,
the gate/up D→F shape) at ~0.95 of peak but kernel B (k-split span-pair
walk — the down projection F→D forward and the dlhs of gate/up read
trans) at ~0.73. This bench isolates kernel B on EXACTLY the 8×1B
QLoRA shapes and sweeps (bm, bk, bn) against the dense padded-dot
bound, the same way ``flash_microbench.py`` established the flash
kernels' floors.

    python -m loadtest.gmm_microbench [--sweep]

Caveat from BASELINE.md / the r4 measurement playbook: microbenchmarks
of pallas kernels overstate per-program overhead ~2× vs the same
kernel inside a full training step — sweep WINNERS must be confirmed
in-step (``loadtest/moe_qlora_8x1b.py``) before being promoted to
defaults.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp


def balanced_offsets(m_real: int, e: int, align: int, key) -> jnp.ndarray:
    """Random near-balanced ALIGN-aligned group offsets covering
    ``m_real`` rows (the route_sorted layout at balanced routing)."""
    import numpy as np

    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 1 << 30)))
    raw = rng.multinomial(m_real // align, [1 / e] * e) * align
    offs = np.concatenate([[0], np.cumsum(raw)]).astype(np.int32)
    offs[-1] = m_real
    return jnp.asarray(offs)


def time_fn(fn, *args, reps: int = 20, warmup: int = 3) -> float:
    """Scan-free repetition timing with a host-transfer sync: ``reps``
    data-dependent calls inside ONE jitted program, so the timed
    window holds one dispatch and ``reps`` kernel executions."""

    @jax.jit
    def run(*a):
        acc = jnp.zeros((), jnp.float32)
        x = a[0]
        for _ in range(reps):
            y = fn(x, *a[1:])
            acc = acc + y.ravel()[0].astype(jnp.float32)
            # serialize: next call's input depends on this output
            x = a[0] + 0.0 * y.ravel()[0].astype(a[0].dtype)
        return acc

    float(run(*args))  # compile + warm
    for _ in range(warmup):
        float(run(*args))
    t0 = time.perf_counter()
    float(run(*args))
    return (time.perf_counter() - t0) / reps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--m", type=int, default=17408)  # 8×1B b2/s4096 M
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--f", type=int, default=8192)
    ap.add_argument("--experts", type=int, default=8)
    args = ap.parse_args()

    from odh_kubeflow_tpu.models.quant import quantize_tensor
    from odh_kubeflow_tpu.ops import pallas_grouped_matmul as pgm

    M, D, F, E = args.m, args.d, args.f, args.experts
    key = jax.random.key(0)
    offs = balanced_offsets(M, E, pgm.ALIGN, jax.random.fold_in(key, 1))

    # the two kernel-B shapes of the 8×1B step:
    #   fwd down:  [M, F] · int8 [E, F, D]           (K=F large → split)
    #   dlhs g/u:  [M, F] · int8 [E, D, F] trans     (same K, same N)
    h = jax.random.normal(key, (M, F), jnp.bfloat16) * 0.3
    down = quantize_tensor(
        jax.random.normal(jax.random.fold_in(key, 2), (E, F, D)) * 0.3
    )
    gate = quantize_tensor(
        jax.random.normal(jax.random.fold_in(key, 3), (E, D, F)) * 0.3
    )

    # dense padded-dot bound: one [M, F]·[F, D] int8-dequant matmul —
    # identical MXU MAC count and identical weight bytes (E× fewer
    # weight reads than the grouped walk only if E blocks were
    # resident; kernel B re-reads each expert's block per row tile it
    # owns, so the bound is optimistic on HBM, exact on MXU)
    wd = down["q"][0]
    sd = down["scale"][0]

    def dense(x, w, s):
        return jax.lax.dot_general(
            x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * s[0][None, :]

    t_dense = time_fn(dense, h, wd, sd)
    flops = 2 * M * F * D

    def run_b(x, q, s, *, trans, bm, bk, bn):
        pairs = pgm.span_pairs(offs, M, bm, include_empty=False)
        return pgm._gmm_b(
            x, q, pairs, offs, trans_rhs=trans, bm=bm, bk=bk, bn=bn,
            interpret=False, scale=s,
        )

    rows = []
    configs = (
        [(512, 1024, 1024)]  # current defaults
        if not args.sweep
        else [
            (bm, bk, bn)
            for bm in (512, 1024)
            for bk in (512, 1024, 2048, 4096)
            for bn in (1024, 2048)
            if bm * bn * 4 * (2048 // bn) <= 8 * 1024 * 1024
        ]
    )
    for bm, bk, bn in configs:
        row = {"bm": bm, "bk": bk, "bn": bn}
        try:
            t_fwd = time_fn(
                functools.partial(
                    run_b, trans=False, bm=bm, bk=bk, bn=bn
                ),
                h, down["q"], down["scale"],
            )
            row["fwd_ms"] = round(t_fwd * 1e3, 3)
            row["fwd_vs_dense"] = round(t_dense / t_fwd, 3)
            row["fwd_tflops"] = round(flops / t_fwd / 1e12, 1)
        except Exception as e:  # noqa: BLE001 — sweep survives bad shapes
            row["fwd_error"] = str(e)[:80]
        try:
            t_dl = time_fn(
                functools.partial(run_b, trans=True, bm=bm, bk=bk, bn=bn),
                h, gate["q"], gate["scale"],
            )
            row["dlhs_ms"] = round(t_dl * 1e3, 3)
            row["dlhs_vs_dense"] = round(t_dense / t_dl, 3)
        except Exception as e:  # noqa: BLE001
            row["dlhs_error"] = str(e)[:80]
        rows.append(row)
        print(json.dumps(row))

    print(json.dumps({
        "m": M, "k": F, "n": D, "experts": E,
        "dense_bound_ms": round(t_dense * 1e3, 3),
        "dense_tflops": round(flops / t_dense / 1e12, 1),
        "configs": rows,
    }))


if __name__ == "__main__":
    main()
