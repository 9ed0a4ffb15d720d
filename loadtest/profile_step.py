"""Profile one trainer step on the attached chip and print a device-time
breakdown by op category — the "profile first" tool VERDICT r3 item 6
asked for (utils/profiling.py capture + trace-event aggregation).

    python -m loadtest.profile_step --config moe --dispatch grouped
    python -m loadtest.profile_step --config 1b16k
    python -m loadtest.profile_step --config 8b16k

Aggregates the XLA device lane(s) of the Chrome trace by HLO op-name
prefix (fusion kernels keep their originating op names), so the output
answers "what fraction of the step is grouped-GEMM vs flash attention
vs routing bookkeeping vs everything else".
"""

from __future__ import annotations

import argparse
import collections
import json
import tempfile

import jax
import jax.numpy as jnp


def _quant(args, default):
    """--quant int8|int4|none (per-config default otherwise)."""
    if args.quant is None:
        return default
    if args.quant == "none":
        return False
    if args.quant not in ("int8", "int4"):
        raise SystemExit(f"--quant must be int8|int4|none, got {args.quant}")
    return args.quant


def build_trainer(args):
    from odh_kubeflow_tpu.models import LoraConfig
    from odh_kubeflow_tpu.models.llama import LlamaConfig
    from odh_kubeflow_tpu.models.moe import MoeConfig
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer

    devices = jax.devices()
    mesh = build_mesh(MeshConfig(fsdp=len(devices)), devices)
    if args.config == "moe":
        cfg = MoeConfig.mixtral_8x1b(
            base=LlamaConfig.llama3_1b(
                dtype=jnp.bfloat16,
                remat_policy=args.policy or "attn",
                remat_pin_layers=args.pin_layers,
            ),
            dispatch=args.dispatch,
            pin_expert_acts=args.pin_expert_acts,
        )
        batch, seq = args.batch or 2, args.seq or 4096
        quant = _quant(args, "int8")
    elif args.config == "1b16k":
        cfg = LlamaConfig.llama3_1b(
            dtype=jnp.bfloat16,
            remat_policy=args.policy or "attn",
            remat_pin_layers=args.pin_layers,
        )
        batch, seq = args.batch or 1, args.seq or 16384
        quant = _quant(args, False)
    elif args.config == "8b16k":
        cfg = LlamaConfig.llama3_8b(
            dtype=jnp.bfloat16,
            remat_policy=args.policy or "none",
            remat_pin_layers=args.pin_layers,
            remat_prefix_policy=args.prefix_policy or "none",
        )
        batch, seq = args.batch or 1, args.seq or 16384
        quant = _quant(args, "int8")
    else:
        raise SystemExit(f"unknown --config {args.config}")
    trainer = Trainer(
        cfg,
        TrainConfig(warmup_steps=2, total_steps=100),
        lora_cfg=LoraConfig(rank=16),
        mesh=mesh,
        quantize_base=quant,
    )
    return trainer, batch, seq


CATEGORIES = (
    # (label, substrings matched against the trace event name, lowercased)
    ("grouped_gemm", ("gmm", "grouped")),
    ("flash_attn", ("flash", "mha", "attn_fwd", "attn_bwd")),
    ("routing", ("sort", "cumsum", "one_hot", "scatter", "gather", "argsort",
                  "iota", "take", "dynamic-update", "dynamic_update")),
    ("matmul", ("dot", "conv", "einsum", "matmul")),
    ("loss", ("log_softmax", "logsumexp", "softmax", "cross")),
    ("copy_convert", ("copy", "convert", "transpose", "bitcast", "reshape",
                       "broadcast", "pad", "slice", "concatenate")),
    ("collective", ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective", "psum")),
)


def categorize(name: str) -> str:
    low = name.lower()
    for label, keys in CATEGORIES:
        if any(k in low for k in keys):
            return label
    return "other"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="moe")
    ap.add_argument("--dispatch", default="grouped")
    ap.add_argument("--pin-expert-acts", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--pin-layers", type=int, default=None)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--quant", default=None, help="int8|int4|none")
    ap.add_argument("--prefix-policy", default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    from odh_kubeflow_tpu.utils import profiling

    trainer, batch, seq = build_trainer(args)
    fake = trainer.make_fake_batch(batch, seq)
    # warm: compile + one steady-state step
    for _ in range(2):
        metrics = trainer.train_step(fake)
    float(metrics["loss"])  # host fetch: waits for the queued steps

    logdir = tempfile.mkdtemp(prefix="prof_")
    with jax.profiler.trace(logdir):
        metrics = trainer.train_step(fake)
        float(metrics["loss"])

    events = profiling.latest_trace_events(logdir)
    # device lanes: pick pids whose process name mentions TPU/device; in
    # jax traces the XLA op lane has tid names like "XLA Ops"; fall back
    # to "all complete events that are not python threads".
    proc_names = {}
    thread_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc_names[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            thread_names[(e["pid"], e.get("tid"))] = e["args"].get("name", "")
    device_pids = {
        pid for pid, n in proc_names.items()
        if "TPU" in n or "/device" in n.lower() or "xla" in n.lower()
    }
    # events nest (while bodies, checkpoint regions wrap their ops):
    # aggregate *self* time per lane — an event's duration minus its
    # direct children's — so nothing is counted twice.
    lanes = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        tname = thread_names.get((e["pid"], e.get("tid")), "")
        low = tname.lower()
        if "step" in low or "module" in low:  # roll-up lanes double-count
            continue
        lanes[(e["pid"], e.get("tid"))].append(e)
    by_cat = collections.Counter()
    by_name = collections.Counter()
    total = 0.0
    for lane_events in lanes.values():
        lane_events.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
        stack = []  # (end_ts, child_time_accum index into records)
        records = []  # mutable [name, dur_us, child_us]
        for e in lane_events:
            ts, dur = e["ts"], e.get("dur", 0)
            while stack and ts >= stack[-1][0]:
                stack.pop()
            if stack:
                records[stack[-1][1]][2] += dur
            records.append([e.get("name", "?"), dur, 0])
            stack.append((ts + dur, len(records) - 1))
        for name, dur, child in records:
            self_s = max(dur - child, 0) / 1e6
            by_cat[categorize(name)] += self_s
            by_name[name] += self_s
            total += self_s
    print(json.dumps({
        "config": args.config,
        "batch": batch, "seq": seq,
        "device_time_s": round(total, 4),
        "by_category": {
            k: round(v, 4) for k, v in by_cat.most_common()
        },
        "lanes": sorted(
            {thread_names.get((e["pid"], e.get("tid")), "?")
             for e in events
             if e.get("ph") == "X" and e.get("pid") in device_pids}
        ),
    }, indent=2))
    # map opaque trace names (fusion.N, closed_call.N) to their HLO
    # long names / source ops via the event args
    arg_info = {}
    for e in events:
        if e.get("ph") == "X" and e.get("args"):
            a = e["args"]
            info = a.get("long_name") or a.get("hlo_op") or a.get(
                "tf_op") or a.get("source") or ""
            if info and e["name"] not in arg_info:
                arg_info[e["name"]] = str(info)[:160]
    for name, dur in by_name.most_common(args.top):
        print(f"{dur*1e3:9.2f} ms  {name[:60]:60s} {arg_info.get(name, '')}")


if __name__ == "__main__":
    main()
