"""Continuous-batching decode engine (VERDICT r2 item 10).

``generate()`` decodes one request batch start-to-finish; under
concurrent load that serialises requests behind each other even though
a decode step for 4 cache slots costs barely more than for 1 (decode
is weight-streaming-bound — the HBM reads of the layer weights
dominate, and they are shared across the batch). This engine keeps a
persistent slot-batched KV cache on device and **admits new streams
into the running decode loop**:

- ``n_slots`` cache slots, each an independent stream with its own
  write offset, rope position, remaining-token budget, eos id, and
  sampling params (temperature / top-k / top-p are [slot] vectors, so
  heterogeneous requests share one compiled step);
- the engine thread alternates *admit* (a prefill program per prompt
  bucket writes one prompt's KV into a free slot) and *decode chunks*
  (one jitted program advancing ALL active slots ``chunk`` tokens);
- a prefill's first token is streamed when the prefill ends: the turn
  dispatches the chunk behind the prefills, fetches and emits each
  first token as its prefill ends, and only then blocks on the chunk;
- the device has a program queued whenever the host knows one: what
  needs no token of the chunk in flight goes out BEHIND that chunk,
  before its fetch (the next part of an admission in parts: its
  prompt, its private cache and its offset were all known before the
  chunk was), and a chunk's tokens are SETTLED at the fetch (counted
  for their requests, ended requests' slots freed, counters moved: all
  the next admit phase reads) and PUBLISHED (appended to their
  requests, stamped, put on their streams, requests finished) once the
  next turn's prefills and chunk have been dispatched. A decode chunk
  itself is never dispatched before the last one's tokens are settled:
  which slots it would decode, and which arrivals it would leave
  waiting, are in those tokens;
- the loop accounts for what it did not decode: every slot-step of
  every chunk is counted under one of ``SLOT_STATES`` and every waiting
  request's seconds are charged to the lane or to the slots, once a
  chunk and once a turn, and each ``engine.turn`` span carries the
  running totals (``_turn_totals``);
- static shapes throughout: one prefill program a prompt bucket and
  two decode chunks (sampled, greedy), plus, with ``prefill_chunk``,
  the interior part's program and one at ``prefill_chunk`` itself
  where that is no bucket: a whole prompt and the last part of a
  prompt admitted in parts go through the SAME program of the
  smallest bucket that holds them (a fresh batch-1 cache at offset 0,
  or the cache the parts filled at theirs). Independent of request mix
  (XLA discipline — no shape depends on arrival order or request
  params);
- per-request ``max_tokens``/``eos`` honored exactly — a slot that
  finishes mid-chunk goes inactive (its writes stop mutating valid
  state) and frees at the next chunk boundary.

No reference counterpart (SURVEY.md §2.4 — the reference has no
inference path); the design is the standard TPU serving pattern
(slot-based batching as in JetStream-class servers), rebuilt minimal.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import threading
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from odh_kubeflow_tpu.models.generate import (
    cache_bytes,
    family_forward,
    init_cache,
)
from odh_kubeflow_tpu.models.llama import (
    INDEXED,
    STATE,
    LlamaConfig,
    kind_of,
    layer_kinds,
    stack_kind,
)
from odh_kubeflow_tpu.ops.select import _key_value, _largest_key, _ordered_keys
from odh_kubeflow_tpu.utils import prometheus, tracing
from odh_kubeflow_tpu.utils.compile_cache import install_process_cache
from odh_kubeflow_tpu.utils.profiling import hot_span

Params = dict[str, Any]

# Names of the jitted programs as a profile's ``XLA Modules`` line (and
# the compile log) shows them, ``jit_<name>``: the decode chunk under
# exactly this name, and every program of the prefill family (a bucket's
# own: a whole prompt or a final part; with a cached prefix, an interior
# part, the prefix seeding, the draft's prefill) with this substring in
# its name, which ends in ``_<positions it runs>``.
DECODE_PROGRAM = "_decode_chunk"
PREFILL_PROGRAM_TAG = "_prefill"

# What the cached forward counts a call (leaves of the cache that are no
# stacks): a decode chunk zeroes them and the host reads them with its
# tokens. Held experts' assignments; an indexer's positions seen and
# attended.
CALL_COUNTERS = ("moe_stats", "sel_stats")

# What a slot did in one step of a decode chunk (``DecodeEngine.slot_steps``
# counts every one of a chunk's ``chunk x n_slots`` under exactly one):
# it emitted a token; it held a decoding request that had ended; the
# admission in parts under way held it; it was free while a prompt that
# needs the part-by-part lane waited for that lane; it was free and
# nothing waited.
SLOT_STATES = ("live", "ended", "admitting", "free_lane", "free_no_work")

# TTFT spans fast warm admissions to cold-compile prefills
_TTFT_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)
# inter-token gaps are near-zero within a fetched chunk and a chunk
# step at boundaries (bimodal — the p95 is the SLO number)
_ITL_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
)


def mask_logits_rowwise(
    logits: jnp.ndarray,  # [B, V] float32
    temperature: jnp.ndarray,  # [B] f32; <=0 → the row is not scaled
    top_k: jnp.ndarray,  # [B] i32; <=0 → off
    top_p: jnp.ndarray,  # [B] f32; <=0 or >=1 → off
) -> jnp.ndarray:
    """The rows scaled by their temperature, with ``-inf`` wherever a
    row's top-k or nucleus leaves a token out: what the sampler draws
    from. Same survivors as ``generate.sample_logits`` row by row, top-p
    over the top-k-filtered row, ties at either edge all kept.

    Neither cut-off is read from a sorted row. A float32's bits, flipped
    so that their integer order is the float order (``_ordered_keys``),
    make a threshold a 32-bit integer, and ``_largest_key`` settles it in
    ``32 / _SEARCH_BITS`` passes of compares and row reductions: the
    k-th largest value is the largest key that at least k keys reach (a
    count: exact), the nucleus's edge the largest key whose keys at or
    above it hold at least ``top_p`` of the row's mass (a masked sum in
    one fixed order, so monotone in the key). The cost is the same for a
    flat row as for a peaked one. The masks themselves compare FLOATS
    against the value the key stands for, so ``-0.0`` and ``+0.0``, two
    keys, stay one value."""
    B, V = logits.shape
    t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / t
    keys = _ordered_keys(scaled)

    # top-k: mask below each row's k-th value (k<=0 → keep all)
    k = jnp.clip(top_k, 1, V)
    kth = _key_value(_largest_key(
        lambda c: jnp.sum(keys >= c[:, None], axis=-1, dtype=jnp.int32) >= k,
        B,
    ))
    scaled = jnp.where(
        (top_k > 0)[:, None] & (scaled < kth[:, None]), -jnp.inf, scaled
    )
    # top-p over the top-k-FILTERED distribution (same composition
    # order as generate.sample_logits: the nucleus mass is computed on
    # the renormalised survivors, not the raw distribution). A filtered
    # token weighs 0 whatever its key, so the keys are not made again
    weight = jnp.exp(scaled - jnp.max(scaled, axis=-1, keepdims=True))
    need = top_p * jnp.sum(weight, axis=-1)
    edge = _key_value(_largest_key(
        lambda c: jnp.sum(
            jnp.where(keys >= c[:, None], weight, 0.0), axis=-1
        ) >= need,
        B,
    ))
    cutoff = jnp.where((top_p > 0) & (top_p < 1), edge, -jnp.inf)
    return jnp.where(scaled < cutoff[:, None], -jnp.inf, scaled)


def sample_logits_rowwise(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] f32; <=0 → greedy for that row
    top_k: jnp.ndarray,  # [B] i32; <=0 → off
    top_p: jnp.ndarray,  # [B] f32; <=0 or >=1 → off
) -> jnp.ndarray:
    """Per-row sampling: each slot applies its own request's knobs.
    Same semantics as ``generate.sample_logits`` row-wise. The top-k and
    nucleus thresholds are searched for over the logits' ordered bit
    patterns, never read from a sorted row, and the masks compare floats
    (``mask_logits_rowwise``)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = mask_logits_rowwise(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


@dataclasses.dataclass
class _Request:
    prompt: list[int]
    max_tokens: int
    temperature: float
    top_k: int
    top_p: float
    eos_id: int  # -1 = none
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    tokens: list[int] = dataclasses.field(default_factory=list)
    error: Optional[Exception] = None
    # set by submit(stream=True): tokens are ALSO pushed here as they
    # decode; a None sentinel marks end-of-stream (check .error then)
    token_q: Optional["queue.Queue"] = None
    cancelled: bool = False
    # SLO observability: wall-clock submit time and per-token emit
    # times (monotonic seconds, host-side — i.e. what a client
    # streaming from this process would see, chunk bursts included)
    submit_t: float = 0.0
    times: list[float] = dataclasses.field(default_factory=list)
    # the request's identifier, and the id of its ``engine.request``
    # trace (recorded when it finishes, from the stamps below)
    request_id: str = dataclasses.field(default_factory=tracing.new_trace_id)
    submit_wall: float = 0.0  # submit_t on the wall clock
    # when the loop took it from the queue with a slot free (monotonic)
    admit_t: Optional[float] = None
    # when the loop set it aside for the part-by-part lane, if it did
    held_t: Optional[float] = None
    finish_t: Optional[float] = None
    slot: int = -1
    bucket: int = -1
    prefix_hit: bool = False
    # set when the request failed because the ENGINE did (a device
    # failure in some turn, or a stop), through no fault of its own:
    # the trace id of the ``engine.turn`` that failed, "" for a stop
    failed_by: Optional[str] = None

    @property
    def complete(self) -> bool:
        """Every token it asked for, or its eos, has been emitted."""
        return self.completed_by(())

    def completed_by(self, more: Sequence[int]) -> bool:
        """``complete`` as it will read once ``more`` have been emitted
        too."""
        return len(self.tokens) + len(more) >= self.max_tokens or (
            list((more or self.tokens)[-1:]) == [self.eos_id]
        )

    def cancel(self) -> None:
        """Abandon the stream (client went away): the engine frees the
        slot at the next chunk boundary instead of decoding the rest
        of max_tokens for nobody."""
        self.cancelled = True

    def ttft(self) -> float:
        """Time to first token (s) — submit → first emitted token."""
        assert self.times, "no tokens emitted"
        return self.times[0] - self.submit_t

    def itls(self) -> list[float]:
        """Inter-token latencies (s) as observed by a streaming
        client: gaps between consecutive token emissions. Chunked
        decode emits in bursts, so the distribution is bimodal —
        near-zero within a fetched chunk, the chunk step time at
        boundaries; the p95 is what an SLO cares about."""
        return [
            b - a for a, b in zip(self.times, self.times[1:])
        ]

    def _emit(self, tok: int) -> None:
        self.tokens.append(tok)
        self.times.append(time.monotonic())
        if self.token_q is not None:
            self.token_q.put(tok)

    def _finish(self) -> None:
        if self.finish_t is None:
            self.finish_t = time.monotonic()
            _record_request(self)
        self.done.set()
        if self.token_q is not None:
            self.token_q.put(None)

    def result(self, timeout: Optional[float] = None) -> list[int]:
        if not self.done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error is not None:
            raise self.error
        return self.tokens

    def iter_tokens(self, timeout: float = 600.0):
        """Generator over tokens as they decode (stream=True submits
        only). Raises the stream's error, if any, at the end."""
        assert self.token_q is not None, "submit with stream=True"
        while True:
            tok = self.token_q.get(timeout=timeout)
            if tok is None:
                break
            yield tok
        if self.error is not None:
            raise self.error


def _splice_slot(cache: Params, sub_cache: Params, slot) -> Params:
    """A batch-1 cache written into row ``slot`` of the slots' cache:
    every stack of every kind of layer (``llama.CACHE_KINDS``: keys and
    values, rings, a recurrent layer's state), each of which has the
    slots behind its layers; what is not a stack (a call's counters)
    stays the slots' own. The whole row is replaced, so nothing of the
    slot's last request is left in it."""
    return {
        name: jax.lax.dynamic_update_slice(
            leaf, sub_cache[name], (0, slot) + (0,) * (leaf.ndim - 2)
        ) if stack_kind(name) else leaf
        for name, leaf in cache.items()
    }


def _program(fn, name: str, **static):
    """``fn`` with ``static`` bound, under ``name``: jax names a jitted
    program after its function, and a bare ``functools.partial`` has no
    name (the program would be ``jit__unknown`` in every trace)."""
    bound = functools.partial(fn, **static)
    bound.__name__ = name
    return bound


def _record_request(req: _Request) -> None:
    """The request's trace, written once, when it finishes, from the
    stamps it carries (no span is held open across loop turns): root
    ``engine.request`` (submit to finish) over ``engine.request.queued``
    (submit to the loop taking it with a slot free; attr ``held_s``: how
    much of that it sat in ``_held``, waiting for the part-by-part lane
    and not for a slot), ``engine.request.first_token`` (from there to
    the emit of its first token: the turn's prefills up to its own, and
    its fetch) and
    ``engine.request.decode`` (first token to finish). A request that
    ends early has the phases it reached, the last one cut at the end.
    Children first: the root's outcome decides whether the collector
    keeps the trace, and it pulls them from the ring. Only a request
    whose own admission raised closes with status ``error`` (and is
    kept): one taken down with the engine has ``outcome=failed`` and
    ``failed_by`` = the trace of the turn that failed, which IS kept,
    so a failure under a long queue cannot fill the kept store."""
    end = req.finish_t
    first = req.times[0] if req.times else None
    if req.error is not None and not req.cancelled:
        outcome = "failed"
    elif req.cancelled and not req.complete:
        outcome = "cancelled"
    else:
        outcome = "finished"
    root_id = tracing.new_span_id()

    def record(name, t_from, t_to, parent, **kw):
        tracing.record_span(tracing.SpanRecord(
            trace_id=req.request_id,
            span_id=root_id if not parent else tracing.new_span_id(),
            parent_span_id=parent,
            name=name,
            start=req.submit_wall + (t_from - req.submit_t),
            duration=t_to - t_from,
            start_mono=t_from,
            **kw,
        ))

    taken = end if req.admit_t is None else req.admit_t
    held_s = 0.0 if req.held_t is None else taken - req.held_t
    marks = (
        ("engine.request.queued", req.submit_t, req.admit_t,
         {"held_s": held_s}),
        ("engine.request.first_token", req.admit_t, first, {}),
        ("engine.request.decode", first, end, {}),
    )
    for name, t_from, t_to, attrs in marks:
        if t_from is not None:
            record(
                name, t_from, end if t_to is None else t_to, root_id,
                attrs=attrs,
            )
    attrs = {
        "prompt_len": len(req.prompt),
        "max_tokens": req.max_tokens,
        "tokens": len(req.tokens),
        "outcome": outcome,
    }
    if req.admit_t is not None:
        attrs.update(
            slot=req.slot, bucket=req.bucket, prefix_hit=req.prefix_hit
        )
    own_fault = outcome == "failed" and req.failed_by is None
    if outcome == "failed" and not own_fault:
        attrs["failed_by"] = req.failed_by
    record(
        "engine.request", req.submit_t, end, "",
        status="error" if own_fault else "ok",
        error=(
            f"{type(req.error).__name__}: {req.error}"
            if outcome == "failed" else ""
        ),
        attrs=attrs,
    )


class DecodeEngine:
    """Slot-batched continuous decoding over a persistent KV cache."""

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        *,
        lora: Optional[Params] = None,
        n_slots: int = 4,
        max_len: int = 2048,
        chunk: int = 8,
        prompt_buckets: Sequence[int] = (64, 256, 1024),
        pad_id: int = 0,
        cache_dtype=jnp.bfloat16,
        seed: int = 0,
        prefill_chunk: Optional[int] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        prefix_cache_entries: int = 0,
        prefix_buckets: Sequence[int] = (256, 512),
        draft_params: Optional[Params] = None,
        draft_cfg: Optional[LlamaConfig] = None,
        spec_k: int = 4,
        spec_rounds_per_call: int = 4,
        metrics_registry: Optional[prometheus.Registry] = None,
    ):
        # persistent XLA compile cache (warmup/ subsystem): the serving
        # path's prefill/decode programs are the biggest cold-start
        # compiles after the train step. The directory is placed from
        # outside (JAX_COMPILATION_CACHE_DIR) or is the fixed
        # in-checkout one — never chosen here.
        install_process_cache()

        self.params = params
        self.cfg = cfg
        self.lora = lora
        self.n_slots = n_slots
        self.max_len = max_len
        self.chunk = chunk
        self.prompt_buckets = tuple(sorted(prompt_buckets))
        self.pad_id = pad_id
        # Chunked prefill: prompts longer than this admit in
        # ``prefill_chunk``-token parts, one part per engine-loop turn,
        # so active slots keep decoding between parts instead of
        # stalling for the whole prompt's prefill (head-of-line
        # blocking — a 1k-token admission would otherwise freeze every
        # stream for the full prefill). None = whole-prompt admission.
        self.prefill_chunk = prefill_chunk
        # in-flight chunked admission (one at a time): dict with req /
        # slot / sub(cache) / consumed / had_prefix
        self._admitting: Optional[dict] = None
        # prompts that need the part-by-part lane while it is taken, in
        # arrival order: they hold no slot, and shorter prompts behind
        # them go on to the free slots meanwhile (a 12288-token prompt
        # keeps the lane for six loop turns; without this every slot
        # that frees in that time stands empty; PERF.md, PR 26)
        self._held: "collections.deque[_Request]" = collections.deque()
        # prompt-prefix KV reuse: entries keyed on the token tuple of a
        # bucketed prefix; admission with a hit prefills only the
        # remainder (a shared system prompt stops being re-prefilled
        # per request). LRU, host-managed, device-resident KV slices.
        self.prefix_cache_entries = prefix_cache_entries
        self.prefix_buckets = tuple(sorted(prefix_buckets))
        self._prefix_cache: "dict[tuple, dict]" = {}

        # speculative decoding per slot: the draft model proposes
        # spec_k tokens, the target verifies them in ONE k+1-token
        # forward per slot (vector cache offsets), and the accepted
        # prefix + one target token advance the stream. Greedy-only —
        # the engine's shared rng cannot replay per-request sampling
        # through the accept/reject rule, and greedy keeps verify
        # token-exact vs plain decode.
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec_k = spec_k
        # a speculative round is a small program next to the host's
        # dispatch + fetch of it: run several rounds inside one jitted
        # call, exactly as the token path batches `chunk` steps
        self.spec_rounds_per_call = max(1, spec_rounds_per_call)
        if draft_params is not None:
            assert draft_cfg is not None, "draft_params needs draft_cfg"
            _, self._dfwd = family_forward(draft_cfg)

        # multi-chip serving: a mesh shards the persistent cache (slot
        # batch over data/fsdp, KV heads over tensor —
        # ``generate.cache_specs``) and every engine program compiles
        # under the mesh, so an 8B-class model that needs >1 chip gets
        # continuous batching / spec decode / the prefix cache like any
        # single-chip model. The caller passes params already sharded
        # (``parallel.mesh.shard_tree``); the host-side loop is
        # unchanged — one process drives the whole mesh (the standard
        # single-controller JAX serving shape).
        self._mesh = mesh

        cache_cfg, self._fwd = family_forward(cfg)
        self._cache_dtype = cache_dtype
        # the most positions one call writes into a slot before it
        # attends: what a window layer's ring holds beyond its window
        # (``init_cache``). Interior parts are written at multiples of
        # ``prefill_chunk`` from 0 and a final part, no wider, at one
        # more, so none straddles a ring's end as long as
        # ``prefill_chunk`` divides it; a whole prompt starts at 0
        self._widest_part = max(
            self.prompt_buckets + ((prefill_chunk,) if prefill_chunk else ())
        )
        S = n_slots
        # the per-slot control vectors are made on the host and put on
        # the device: a jnp.zeros per shape and dtype is a program of
        # its own to trace, lower and load before the first request
        control = {
            "kv_mask": np.zeros((S, max_len), bool),
            "cur_token": np.zeros((S,), np.int32),
            "write_idx": np.zeros((S,), np.int32),
            "pos": np.zeros((S,), np.int32),
            "active": np.zeros((S,), bool),
            "remaining": np.zeros((S,), np.int32),
            "temp": np.zeros((S,), np.float32),
            "top_k": np.zeros((S,), np.int32),
            "top_p": np.zeros((S,), np.float32),
            "eos": np.full((S,), -1, np.int32),
        }
        self._state = {
            "cache": self._new_cache(cache_cfg, S),
            **jax.device_put(control),
            "rng": jax.random.key(seed),
        }
        if draft_params is not None:
            dcache_cfg, _ = family_forward(draft_cfg)
            self._state["dcache"] = self._new_cache(dcache_cfg, S)
        # what a whole prompt's prefill starts from: a batch-1 cache
        # that nothing has written and offset 0, built once. A bucket's
        # program takes both as arguments and donates neither (the state
        # alone), so every whole prompt is handed the same zeros with no
        # allocation of its own, and the last part of an admission in
        # parts, which brings the cache its parts filled, runs through
        # the very same program
        self._fresh_sub = self._new_cache(cache_cfg, 1)
        self._start0 = jnp.int32(0)
        # a second home for a chunk's counters, made and placed as the
        # cache's own: ``_take_chunk_stats`` swaps the two
        self._stats_spare = {
            name: jnp.zeros_like(self._state["cache"][name])
            for name in CALL_COUNTERS if name in self._state["cache"]
        }
        if mesh is not None:
            from jax.sharding import NamedSharding

            from odh_kubeflow_tpu.models.generate import cache_specs

            cspec = {
                kv: NamedSharding(mesh, s)
                for kv, s in cache_specs(cache_cfg).items()
            }
            rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
            self._state = {
                k: (
                    jax.device_put(v, cspec)
                    if k in ("cache", "dcache")
                    # per-slot control vectors are tiny: replicate
                    else jax.device_put(v, rep)
                )
                for k, v in self._state.items()
            }
            self._stats_spare = {
                name: jax.device_put(spare, cspec[name])
                for name, spare in self._stats_spare.items()
            }
            # one row cannot shard over the slots' axes: every device
            # holds it, placed once and not at each admission
            self._fresh_sub = jax.device_put(self._fresh_sub, rep)
        # the cache by kind of layer, and what the kinds ask of the
        # engine's own indexing
        self.cache_bytes = cache_bytes(self._state["cache"])
        rings = {
            leaf.shape[2] for name, leaf in self._state["cache"].items()
            if stack_kind(name) == "window" and leaf.shape[2] != max_len
        }
        stateful = self.cache_bytes[STATE] > 0
        if (rings or stateful) and (
            prefix_cache_entries
            or any(
                r % w for r in rings
                for w in (self._widest_part, prefill_chunk or 1)
            )
        ):
            raise NotImplementedError(
                "a windowed cache keeps rings and a recurrent layer one "
                "state a slot: a prefix entry cannot be cut from either, "
                "and every prompt bucket and the prefill chunk must "
                f"divide the ring ({sorted(rings)})"
            )
        if stateful and draft_params is not None:
            raise NotImplementedError(
                "speculative verify writes positions it may take back; a "
                "recurrent layer's state has no position to take back to"
            )
        if self.cache_bytes[INDEXED] and (
            prefix_cache_entries or draft_params is not None
        ):
            raise NotImplementedError(
                "a prefix entry holds keys and values by name and would "
                "seed a stream whose indexer keys are zeros; speculative "
                "verify asks an indexed layer for several tokens a row at "
                "per-row offsets, one selection each"
            )
        # counters of what the cached forward adds for a mixture of
        # held experts and for window layers (PERF.md section 3): read
        # with each decode chunk's own fetch
        self.moe_local_assignments = 0
        self.moe_experts_hit = 0
        self.moe_dropped = 0
        self.moe_rows_computed = 0  # rows of the expert kernel's live tiles
        # what the decode steps' queries of indexed layers could see and
        # what they attended, in (slot, layer) positions
        self.sel_causal_rows = 0
        self.sel_attended_rows = 0
        self.window_blocks_skipped = 0
        kinds = layer_kinds(cache_cfg)
        # {window: how many layers of the whole stack have it}
        self._window_layers = collections.Counter(
            [k for k in kinds if kind_of(k) == "window"]
            * (cache_cfg.num_layers // len(kinds))
        )
        # serving SLO metrics (arXiv:2605.25645's TTFT/TPOT surface):
        # the same registry the platform scrapes at /metrics
        reg = metrics_registry or prometheus.default_registry
        self.m_ttft = reg.histogram(
            "serving_ttft_seconds",
            "Time from request submit to first emitted token",
            buckets=_TTFT_BUCKETS,
        )
        self.m_itl = reg.histogram(
            "serving_inter_token_seconds",
            "Gap between consecutive token emissions (streaming-client view)",
            buckets=_ITL_BUCKETS,
        )
        self.m_queue_depth = reg.gauge(
            "serving_queue_depth", "Requests waiting for a decode slot"
        )
        self.m_queue_wait = reg.histogram(
            "serving_queue_wait_seconds",
            "Time from request submit to the loop taking it with a slot free",
            buckets=_TTFT_BUCKETS,
        )
        self.m_occupancy = reg.gauge(
            "serving_batch_occupancy",
            "Fraction of decode slots active after the last chunk",
        )
        self.m_slot_steps = reg.counter(
            "serving_slot_steps_total",
            "Slot-steps of the decode chunks by what the slot did in them",
            labelnames=("state",),
        )
        self.m_lane_held = reg.gauge(
            "serving_lane_held",
            "Requests set aside for the part-by-part admission lane",
        )
        # observability: decode_steps × n_slots is the work a serial
        # server would have spent per-request; the ratio
        # tokens_emitted / decode_steps is the batching efficiency
        self.decode_steps = 0
        self.decode_calls = 0  # chunk programs those steps came in
        # ... and those of them that ran the sampler (the general
        # program; the others were all-greedy or a draft's rounds)
        self.decode_calls_sampled = 0
        self.tokens_emitted = 0
        # loop turns that had work, and prefill programs dispatched
        # (whole prompts, parts of a chunked admission, prefix seeding)
        self.turns = 0
        self.prefill_calls = 0
        # prompt tokens those programs ran, the positions they ran them
        # in (a bucket's or a part's width) and the causal (query, key)
        # pairs of those tokens, a layer: all, and those of the programs
        # that started a stream (a whole prompt, a first part)
        self.prefill_tokens = 0
        self.prefill_positions = 0
        self.prefill_pairs = 0
        self.prefill_pairs_first = 0
        # first tokens emitted ahead of their turn's chunk fetch: every
        # request that reached a slot with max_tokens > 1
        self.first_tokens_early = 0
        # parts of admissions in parts dispatched (interior and final),
        # and those of them that went out behind a decode chunk before
        # its fetch, so that the device walks from the chunk into the
        # part with no host in between
        self.parts = 0
        self.parts_ahead = 0
        # every slot-step of every decode chunk under the one state it
        # was in, closed chunk by chunk at the settle (not beside a
        # draft, whose rounds are no steps)
        self.slot_steps = dict.fromkeys(SLOT_STATES, 0)
        # what the waiting requests waited for, in request-seconds: the
        # lane (in ``_held`` with a slot free) or a slot (none free),
        # charged at the end of each top admit phase for the time since
        # the last one
        self.wait_lane_s = 0.0
        self.wait_slot_s = 0.0
        self._wait_stamp = time.monotonic()
        # the admission's part for the coming turn has already gone out
        self._part_ahead = False
        # the last chunk's tokens, settled and not yet published (nor
        # in ``req.tokens``): ([(req, tokens, ended)], slots then held)
        self._settled: Optional[tuple] = None
        # set on unrecoverable device failure; submit() then raises
        self.failure: Optional[Exception] = None
        self._slot_req: list[Optional[_Request]] = [None] * S
        # (req, device-scalar first token, slot) of the prefills this
        # turn dispatched: fetched and emitted one by one once the chunk
        # has been dispatched behind them, before the chunk's own fetch
        self._pending_first: list = []
        self._queue: "queue.Queue[Optional[_Request]]" = queue.Queue()
        self._wake = threading.Event()
        self._stopped = False
        self._prefill_fns: dict[int, Any] = {}
        self._decode_fn = jax.jit(
            _program(self._decode_chunk, DECODE_PROGRAM), donate_argnums=1
        )
        self._decode_greedy_fn = jax.jit(
            _program(
                self._decode_chunk, f"{DECODE_PROGRAM}_greedy", greedy=True
            ),
            donate_argnums=1,
        )
        self._spec_fn = (
            jax.jit(self._spec_chunk, donate_argnums=1)
            if draft_params is not None
            else None
        )
        self._draft_prefill_fns: dict[int, Any] = {}
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _new_cache(self, cache_cfg, batch: int) -> Params:
        """The one allocator of KV cache: the slots' and, batch 1, an
        admission's, of the same kinds and rings."""
        return init_cache(
            cache_cfg, batch, self.max_len, self._cache_dtype,
            widest_part=self._widest_part,
        )

    # -- jitted programs ----------------------------------------------------

    def _write_slot_state(self, state, sub_cache, kv_mask1, slot, first,
                          total, req_vec, rng):
        """Splice a freshly prefilled (sub_cache, kv_mask) into ``slot``
        and arm its per-request decode fields — shared by the cold and
        prefix-cache admission paths so their semantics cannot drift."""
        max_tokens, temp, top_k, top_p, eos = req_vec
        st = dict(state)
        st["rng"] = rng
        st["cache"] = _splice_slot(state["cache"], sub_cache, slot)
        st["kv_mask"] = jax.lax.dynamic_update_slice(
            state["kv_mask"], kv_mask1, (slot, 0)
        )
        at = lambda name, v: state[name].at[slot].set(v)  # noqa: E731
        st["cur_token"] = at("cur_token", first)
        st["write_idx"] = at("write_idx", total)
        st["pos"] = at("pos", total)
        # the prefill itself emits the first token
        st["remaining"] = at("remaining", max_tokens - 1)
        finished = (max_tokens <= 1) | (first == eos)
        st["active"] = at("active", ~finished)
        st["temp"] = at("temp", temp)
        st["top_k"] = at("top_k", top_k)
        st["top_p"] = at("top_p", top_p)
        st["eos"] = at("eos", eos)
        return st, first


    @staticmethod
    def _unpack_admission(packed, bucket):
        """One host→device transfer per admission: ``packed`` [1,
        bucket+7] int32 = padded prompt ‖ [L, slot, max_tokens, top_k,
        eos, temp_bits, top_p_bits] (floats bit-cast): the prompt and
        its six request scalars travel as one array, not seven."""
        prompt = packed[:, :bucket]
        meta = packed[0, bucket:]
        length, slot, max_tokens, top_k, eos = (
            meta[0], meta[1], meta[2], meta[3], meta[4]
        )
        temp = jax.lax.bitcast_convert_type(meta[5], jnp.float32)
        top_p = jax.lax.bitcast_convert_type(meta[6], jnp.float32)
        return prompt, length, slot, (max_tokens, temp, top_k, top_p, eos)

    @staticmethod
    def pack_admission(prompt, pad_id, bucket, req):
        meta = np.asarray(
            [
                len(prompt), 0, req.max_tokens, req.top_k, req.eos_id,
                np.float32(req.temperature).view(np.int32),
                np.float32(req.top_p).view(np.int32),
            ],
            np.int32,
        )
        row = np.concatenate(
            [
                np.asarray(
                    prompt + [pad_id] * (bucket - len(prompt)), np.int32
                ),
                meta,
            ]
        )
        return row[None, :]

    def _prefill_tail(self, params, lora, state, sub_cache, packed,
                      start, *, bucket):
        """Run the FINAL (possibly only) prompt segment — ``packed``'s
        remainder tokens at traced cache offset ``start`` — through an
        already-seeded batch-1 ``sub_cache``, sample the first token,
        and splice the finished slot into ``state`` (the slot carried
        in ``packed``, see ``_unpack_admission``). Shared tail of
        every admission flavor, so their semantics cannot drift: cold
        (``_fresh_sub`` at ``_start0``) and chunked (the cache
        ``_prefill_part`` calls filled) call it as ``bucket``'s ONE
        program (``_prefill_runner``), prefix-hit from ``_prefill_ext``
        (cache seeded with the prefix KV)."""
        prompt_rem, rem_len, slot, req_vec = self._unpack_admission(
            packed, bucket
        )
        max_tokens, temp, top_k, top_p, eos = req_vec
        S_b = prompt_rem.shape[1]
        total = start + rem_len
        slots_row = jnp.arange(self.max_len, dtype=jnp.int32)[None, :]
        kv_mask1 = slots_row < total
        positions = start + jnp.arange(S_b, dtype=jnp.int32)[None, :]
        logits, sub_cache = self._fwd(
            params, prompt_rem, self.cfg, sub_cache, start,
            positions=positions, kv_mask=kv_mask1, lora=lora,
            # bucket padding is not content: the MoE router must not
            # let pad positions consume expert capacity
            token_mask=(
                jnp.arange(S_b, dtype=jnp.int32) < rem_len
            )[None],
        )
        last = jnp.take_along_axis(
            logits, (rem_len - 1)[None, None, None], axis=1
        )[:, 0, :]
        rng, sub = jax.random.split(state["rng"])
        with jax.named_scope("sampler"):
            first = sample_logits_rowwise(
                last, sub, temp[None], top_k[None], top_p[None]
            )[0]
        return self._write_slot_state(
            state, sub_cache, kv_mask1, slot, first, total, req_vec, rng
        )

    def _prefill_part(self, params, lora, sub_cache, toks, start, *,
                      width: int):
        """One FULL interior segment of a chunked admission: ``width``
        prompt tokens written into the batch-1 ``sub_cache`` at traced
        offset ``start``. No sampling, no slot splice — interior parts
        only extend the KV; ``_prefill_tail`` finishes the admission.
        One compile total (start is traced), independent of prompt
        length."""
        slots_row = jnp.arange(self.max_len, dtype=jnp.int32)[None, :]
        kv_mask1 = slots_row < (start + width)
        positions = start + jnp.arange(width, dtype=jnp.int32)[None, :]
        _, sub_cache = self._fwd(
            params, toks, self.cfg, sub_cache, start,
            positions=positions, kv_mask=kv_mask1, lora=lora,
            token_mask=jnp.ones((1, width), jnp.bool_),
        )
        return sub_cache

    def _decode_chunk(self, params_lora, state, *, greedy: bool = False):
        params, lora = params_lora
        # a chunk's own counters: zeroed here, read with its tokens
        state = dict(state, cache={
            name: jnp.zeros_like(leaf) if name in CALL_COUNTERS else leaf
            for name, leaf in state["cache"].items()
        })

        def step(st, _):
            active = st["active"]
            write_idx = st["write_idx"]
            # only active rows extend their valid region
            slots_row = jnp.arange(self.max_len, dtype=jnp.int32)[None, :]
            kv_mask = st["kv_mask"] | (
                active[:, None] & (slots_row == write_idx[:, None])
            )
            logits, cache = self._fwd(
                params,
                st["cur_token"][:, None],
                self.cfg,
                st["cache"],
                write_idx,
                positions=st["pos"][:, None],
                kv_mask=kv_mask,
                lora=lora,
                # a slot that decodes nothing still rides the batch: a
                # router must not count it or read experts for it
                token_mask=active[:, None],
            )
            rng, sub = jax.random.split(st["rng"])
            if greedy:
                # all active slots are temperature<=0: no search for
                # cut-offs and no noise over the vocabulary
                nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(
                    jnp.int32
                )
            else:
                with jax.named_scope("sampler"):
                    nxt = sample_logits_rowwise(
                        logits[:, 0, :], sub, st["temp"], st["top_k"],
                        st["top_p"],
                    )
            remaining = st["remaining"] - active.astype(jnp.int32)
            finished = (nxt == st["eos"]) | (remaining <= 0)
            new_active = active & ~finished
            st = dict(
                st,
                cache=cache,
                kv_mask=kv_mask,
                cur_token=jnp.where(active, nxt, st["cur_token"]),
                write_idx=jnp.where(
                    active, jnp.minimum(write_idx + 1, self.max_len - 1),
                    write_idx,
                ),
                pos=jnp.where(active, st["pos"] + 1, st["pos"]),
                remaining=remaining,
                active=new_active,
                rng=rng,
            )
            # ship the was-active mask alongside: a slot's final token
            # (eos / budget-exhausting) is emitted while still active,
            # and the host must not mistake inactive filler for content
            # (pad_id may be a legal token id)
            return st, (nxt, active)

        state, (toks, mask) = jax.lax.scan(
            step, state, None, length=self.chunk
        )
        return state, (toks.T, mask.T)  # [n_slots, chunk] each

    def _prefill_ext(
        self, params, lora, state, prefix_kv, packed, *, plen: int,
        bucket: int,
    ):
        """Prefill with a cached prefix: ``prefix_kv`` (k/v
        [L, 1, plen, Hkv * hd], a prefix-cache entry) seeds the slot's
        cache and only the remainder tokens run through the model, at
        positions/cache offset ``plen`` (static — one compile per
        (prefix bucket, remainder bucket))."""
        cache_cfg, _ = family_forward(self.cfg)
        sub_cache = self._new_cache(cache_cfg, 1)
        sub_cache = self._prefill_seed(sub_cache, prefix_kv, plen=plen)
        return self._prefill_tail(
            params, lora, state, sub_cache, packed, jnp.int32(plen),
            bucket=bucket,
        )

    def _prefill_seed(self, sub_cache, prefix_kv, *, plen: int):
        """Seed a fresh batch-1 cache with a prefix-cache entry (the
        chunked-admission analogue of _prefill_ext's seeding)."""
        return {
            kv: sub_cache[kv].at[:, :, :plen].set(prefix_kv[kv])
            for kv in ("k", "v")
        }

    def _draft_prefill(self, dparams, state, packed, *, bucket):
        """Fill the DRAFT model's cache for a freshly admitted slot
        over the full prompt (the draft is cheap — even on a
        prefix-cache hit the draft re-prefills from scratch, which is
        what lets prefix entries stay target-only)."""
        prompt, length, slot, _ = self._unpack_admission(packed, bucket)
        dcache_cfg, _ = family_forward(self.draft_cfg)
        sub = self._new_cache(dcache_cfg, 1)
        S_b = prompt.shape[1]
        slots_row = jnp.arange(self.max_len, dtype=jnp.int32)[None, :]
        kv_mask1 = slots_row < length
        positions = jnp.arange(S_b, dtype=jnp.int32)[None, :]
        _, sub = self._dfwd(
            dparams, prompt, self.draft_cfg, sub, jnp.int32(0),
            positions=positions, kv_mask=kv_mask1,
            # an MoE draft's router must not let bucket-padding tokens
            # consume expert capacity (same contract as _prefill_tail)
            token_mask=kv_mask1[:, :S_b],
        )
        st = dict(state)
        st["dcache"] = _splice_slot(state["dcache"], sub, slot)
        return st

    def _draft_prefill_runner(self, bucket: int):
        if bucket not in self._draft_prefill_fns:
            self._draft_prefill_fns[bucket] = jax.jit(
                _program(
                    self._draft_prefill,
                    f"_draft{PREFILL_PROGRAM_TAG}_{bucket}", bucket=bucket,
                ),
                donate_argnums=1,
            )
        return self._draft_prefill_fns[bucket]

    def _spec_chunk(self, params_all, state):
        """``spec_rounds_per_call`` speculative rounds in one jitted
        call. Each round: the draft proposes ``spec_k`` tokens
        (sequential draft decode steps), the target verifies all of
        them in a single k+1-token forward at per-slot offsets, and
        each slot advances by its accepted prefix.

        Greedy acceptance: proposal i stands iff it equals the
        target's own argmax at that position, so emitted tokens are
        token-exact vs plain decode. Emission is capped at k per round
        (the all-accepted bonus token is forfeited) so the draft cache
        never falls behind the stream — the draft wrote slots
        [widx, widx+k) during proposal, and a cap-k advance keeps
        every needed position covered without a catch-up pass.
        """
        params, lora, dparams = params_all
        k = self.spec_k
        S = self.n_slots
        slots_row = jnp.arange(self.max_len, dtype=jnp.int32)[None, :]
        rows = jnp.arange(S)

        def one_round(state, _):
            active = state["active"]
            widx = state["write_idx"]
            pos = state["pos"]

            def dstep(carry, i):
                cur, dcache = carry
                kv_mask = slots_row < (widx + i + 1)[:, None]
                logits, dcache = self._dfwd(
                    dparams, cur[:, None], self.draft_cfg, dcache,
                    widx + i, positions=(pos + i)[:, None],
                    kv_mask=kv_mask,
                )
                nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(
                    jnp.int32
                )
                return (nxt, dcache), nxt

            (_, dcache), props = jax.lax.scan(
                dstep, (state["cur_token"], state["dcache"]),
                jnp.arange(k, dtype=jnp.int32),
            )
            props = props.T  # [S, k]

            tokens_v = jnp.concatenate(
                [state["cur_token"][:, None], props], axis=1
            )  # [S, k+1]
            verify_mask = slots_row < (widx + k + 1)[:, None]
            positions_v = (
                pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            )
            logits_v, cache = self._fwd(
                params, tokens_v, self.cfg, state["cache"], widx,
                positions=positions_v, kv_mask=verify_mask, lora=lora,
            )
            targets = jnp.argmax(logits_v, axis=-1).astype(jnp.int32)

            match = props == targets[:, :k]
            n_acc = jnp.sum(
                jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
            )
            n_eff = jnp.minimum(n_acc + 1, k)
            emit_window = targets[:, :k]
            eos_hit = (emit_window == state["eos"][:, None]) & (
                state["eos"][:, None] >= 0
            )
            any_eos = eos_hit.any(axis=1)
            first_eos = jnp.argmax(eos_hit, axis=1)
            n_eff = jnp.where(
                any_eos, jnp.minimum(n_eff, first_eos + 1), n_eff
            )
            n_eff = jnp.minimum(n_eff, jnp.maximum(state["remaining"], 0))
            n_eff = jnp.where(active, n_eff, 0)

            new_widx = widx + n_eff
            remaining = state["remaining"] - n_eff
            ended = (any_eos & (first_eos < n_eff)) | (remaining <= 0)
            new_active = active & ~ended
            cur_new = jnp.where(
                active & (n_eff > 0),
                emit_window[rows, jnp.clip(n_eff - 1, 0, k - 1)],
                state["cur_token"],
            )
            # contiguous validity [0, new_widx): verify wrote k+1 slots
            # but only the accepted prefix is real stream
            kv_mask_new = slots_row < new_widx[:, None]
            emit_mask = active[:, None] & (
                jnp.arange(k, dtype=jnp.int32)[None, :] < n_eff[:, None]
            )
            st = dict(
                state,
                cache=cache,
                dcache=dcache,
                kv_mask=kv_mask_new,
                cur_token=cur_new,
                write_idx=jnp.minimum(new_widx, self.max_len - 1),
                pos=pos + n_eff,
                remaining=remaining,
                active=new_active,
            )
            return st, (emit_window, emit_mask)

        state, (toks, masks) = jax.lax.scan(
            one_round, state, None, length=self.spec_rounds_per_call
        )
        # [R, S, k] → [S, R·k]: rounds concatenate in stream order
        R = self.spec_rounds_per_call
        toks = jnp.swapaxes(toks, 0, 1).reshape(S, R * k)
        masks = jnp.swapaxes(masks, 0, 1).reshape(S, R * k)
        return state, (toks, masks)

    # -- engine loop --------------------------------------------------------

    def _prefill_runner(self, bucket: int):
        """``bucket``'s one prefill program: a whole prompt's and a
        final part's alike (``_prefill_tail`` at that width)."""
        if bucket not in self._prefill_fns:
            # donate the engine state only: the sub-cache is spliced
            # into state's larger buffers, so its donation could never
            # be used (it would just warn), and ``_fresh_sub`` must
            # outlive the call
            self._prefill_fns[bucket] = jax.jit(
                _program(
                    self._prefill_tail, f"{PREFILL_PROGRAM_TAG}_{bucket}",
                    bucket=bucket,
                ),
                donate_argnums=2,
            )
        return self._prefill_fns[bucket]

    def _prefill_ext_runner(self, plen: int, bucket: int):
        key = (plen, bucket)
        if key not in self._prefill_fns:
            self._prefill_fns[key] = jax.jit(
                _program(
                    self._prefill_ext,
                    f"{PREFILL_PROGRAM_TAG}_ext_{plen}_{bucket}",
                    plen=plen, bucket=bucket,
                ),
                donate_argnums=2,
            )
        return self._prefill_fns[key]

    def _prefill_part_runner(self, width: int):
        key = ("part", width)
        if key not in self._prefill_fns:
            self._prefill_fns[key] = jax.jit(
                _program(
                    self._prefill_part, f"{PREFILL_PROGRAM_TAG}_part_{width}",
                    width=width,
                ),
                donate_argnums=2,
            )
        return self._prefill_fns[key]

    def _prefill_seed_runner(self, plen: int):
        key = ("seed", plen)
        if key not in self._prefill_fns:
            self._prefill_fns[key] = jax.jit(
                _program(
                    self._prefill_seed, f"{PREFILL_PROGRAM_TAG}_seed_{plen}",
                    plen=plen,
                ),
                donate_argnums=0,
            )
        return self._prefill_fns[key]

    def _match_prefix(self, prompt: list[int]):
        """Longest cached bucketed prefix strictly shorter than the
        prompt (the remainder must be non-empty — the model still has
        to produce the first next-token logits)."""
        if not self.prefix_cache_entries:
            return None, None
        for pb in reversed(self.prefix_buckets):
            if len(prompt) <= pb:
                continue
            key = (pb, tuple(prompt[:pb]))
            entry = self._prefix_cache.get(key)
            if entry is not None:
                # LRU touch
                self._prefix_cache[key] = self._prefix_cache.pop(key)
                return pb, entry
        return None, None

    def _maybe_insert_prefix(self, prompt: list[int], slot: int) -> None:
        """After a cold prefill, remember the prompt's bucketed prefix
        KV (sliced out of the slot's freshly written cache) so the
        next request sharing it skips that prefill work."""
        if not self.prefix_cache_entries:
            return
        for pb in reversed(self.prefix_buckets):
            if len(prompt) <= pb:
                continue
            key = (pb, tuple(prompt[:pb]))
            if key in self._prefix_cache:
                return
            entry = {
                kv: jax.lax.dynamic_slice_in_dim(
                    jax.lax.dynamic_slice_in_dim(
                        self._state["cache"][kv], slot, 1, axis=1
                    ),
                    0, pb, axis=2,
                )
                for kv in ("k", "v")
            }
            while len(self._prefix_cache) >= self.prefix_cache_entries:
                self._prefix_cache.pop(next(iter(self._prefix_cache)))
            self._prefix_cache[key] = entry
            return

    def _note_prefill(self, req: _Request, slot: int, bucket: int,
                      prefix_hit: bool, part: str, tokens: int,
                      start: int = 0) -> None:
        """One prefill program is about to be dispatched for ``req``:
        counted (the call, the ``tokens`` of the prompt it runs from
        offset ``start`` on and the ``bucket`` positions it runs them in:
        what lies between is padding, which a recurrent layer's scan
        walks too), stamped on
        the request (for its ``engine.request`` span) and noted as an
        event on the turn's ``engine.admit``."""
        self.prefill_calls += 1
        if part.startswith(("part@", "final")):
            self.parts += 1
        self.prefill_tokens += tokens
        self.prefill_positions += bucket if tokens else 0
        pairs = tokens * start + tokens * (tokens + 1) // 2
        self.prefill_pairs += pairs
        self.prefill_pairs_first += 0 if start else pairs
        req.slot, req.bucket, req.prefix_hit = slot, bucket, prefix_hit
        tracing.add_event(
            "prefill", request=req.request_id, slot=slot, bucket=bucket,
            prefix_hit=prefix_hit, part=part,
        )

    def _admit(self, req: _Request) -> None:
        slot = self._slot_req.index(None)
        L = len(req.prompt)
        plen, entry = self._match_prefix(req.prompt)
        if plen is not None:
            rem = req.prompt[plen:]
            bucket = next(b for b in self.prompt_buckets if len(rem) <= b)
            row = self.pack_admission(rem, self.pad_id, bucket, req)
            row[0, bucket + 1] = slot
            packed = jnp.asarray(row)
            self._note_prefill(req, slot, bucket, True, "whole", len(rem), plen)
            self._state, first = self._prefill_ext_runner(plen, bucket)(
                self.params, self.lora, self._state, entry, packed,
            )
        else:
            bucket = next(b for b in self.prompt_buckets if L <= b)
            row = self.pack_admission(req.prompt, self.pad_id, bucket, req)
            row[0, bucket + 1] = slot
            packed = jnp.asarray(row)
            self._note_prefill(req, slot, bucket, False, "whole", L)
            self._state, first = self._prefill_runner(bucket)(
                self.params, self.lora, self._state, self._fresh_sub,
                packed, self._start0,
            )
            self._maybe_insert_prefix(req.prompt, slot)
        # the first token stays on the device until this turn's chunk
        # has been dispatched behind the prefill (``_turn`` fetches it
        # then, ahead of the chunk), unless the request can't enter a
        # slot at all. Checked BEFORE the draft prefill — a
        # max_tokens<=1 request never decodes, so filling a draft cache
        # for it (plus possibly a fresh bucket compile) would be pure
        # waste.
        if req.max_tokens <= 1:
            tok = int(first)
            req._emit(tok)
            self._observe_emit(req)
            req._finish()
            return
        if self.draft_params is not None:
            full_bucket = next(b for b in self.prompt_buckets if L <= b)
            if plen is None and full_bucket == bucket:
                # cache-miss path: the target admission row is the
                # same full prompt in the same bucket — one upload,
                # not two (a prefix HIT's row holds only the remainder,
                # so it is never reusable here)
                drow = packed
            else:
                row = self.pack_admission(
                    req.prompt, self.pad_id, full_bucket, req
                )
                row[0, full_bucket + 1] = slot
                drow = jnp.asarray(row)
            self._state = self._draft_prefill_runner(full_bucket)(
                self.draft_params, self._state, drow,
            )
        self._slot_req[slot] = req  # claim before the next admission
        self._pending_first.append((req, first, slot))

    def _begin_chunked_admit(self, req: _Request) -> None:
        """Reserve a slot and set up the part-by-part admission: the
        slot stays device-inactive (no emissions) until the final part
        splices it in, and decode chunks run between parts."""
        slot = self._slot_req.index(None)
        cache_cfg, _ = family_forward(self.cfg)
        sub_cache = self._new_cache(cache_cfg, 1)
        start = 0
        plen, entry = self._match_prefix(req.prompt)
        if plen is not None:
            self._note_prefill(req, slot, plen, True, "seed", 0)
            sub_cache = self._prefill_seed_runner(plen)(sub_cache, entry)
            start = plen
        self._slot_req[slot] = req  # reserve; device-inactive until final
        self._admitting = dict(
            req=req, slot=slot, sub=sub_cache, consumed=start,
            had_prefix=plen is not None,
        )

    def _admit_step(self) -> None:
        """Advance the in-flight chunked admission by ONE part (called
        once per engine-loop turn, between decode chunks — the
        anti-head-of-line-blocking contract)."""
        adm = self._admitting
        req, slot = adm["req"], adm["slot"]
        if req.cancelled:
            self._admitting = None
            self._slot_req[slot] = None
            req._finish()
            return
        C = self.prefill_chunk
        consumed = adm["consumed"]
        L = len(req.prompt)
        if L - consumed > C:
            seg = jnp.asarray(
                [req.prompt[consumed:consumed + C]], jnp.int32
            )
            self._note_prefill(
                req, slot, C, adm["had_prefix"], f"part@{consumed}", C, consumed
            )
            adm["sub"] = self._prefill_part_runner(C)(
                self.params, self.lora, adm["sub"], seg,
                jnp.int32(consumed),
            )
            adm["consumed"] = consumed + C
            return
        # final part: remainder ≤ C — sample + splice into the slot,
        # at the smallest bucket that holds the remainder (a part's
        # whole width only where no bucket that narrow does)
        rem = req.prompt[consumed:]
        bucket = next(
            (b for b in self.prompt_buckets if len(rem) <= b <= C), C
        )
        row = self.pack_admission(rem, self.pad_id, bucket, req)
        row[0, bucket + 1] = slot
        packed = jnp.asarray(row)
        self._note_prefill(
            req, slot, bucket, adm["had_prefix"], "final", len(rem), consumed
        )
        self._state, first = self._prefill_runner(bucket)(
            self.params, self.lora, self._state, adm["sub"], packed,
            jnp.int32(consumed),
        )
        self._admitting = None
        if not adm["had_prefix"]:
            self._maybe_insert_prefix(req.prompt, slot)
        if req.max_tokens <= 1:
            self._slot_req[slot] = None
            req._emit(int(first))
            self._observe_emit(req)
            req._finish()
            return
        if self.draft_params is not None:
            full_bucket = next(
                b for b in self.prompt_buckets if L <= b
            )
            drow = self.pack_admission(
                req.prompt, self.pad_id, full_bucket, req
            )
            drow[0, full_bucket + 1] = slot
            self._state = self._draft_prefill_runner(full_bucket)(
                self.draft_params, self._state, jnp.asarray(drow),
            )
        self._pending_first.append((req, first, slot))

    def _observe_emit(self, req: _Request) -> None:
        """Feed the SLO histograms after a ``req._emit``: the first
        token is the request's TTFT, every later one an inter-token
        gap (exactly what a streaming client measures)."""
        if len(req.times) == 1:
            self.m_ttft.observe(req.times[0] - req.submit_t)
        else:
            self.m_itl.observe(req.times[-1] - req.times[-2])

    def _fail_engine(self, exc: Exception) -> None:
        """A device-level failure (OOM, preemption, XLA runtime error)
        anywhere in the loop is fatal: the jitted programs donate the
        state buffers, so after a failed execution ``self._state`` may
        reference deleted memory. Fail every in-flight and queued
        request immediately (their ``result()`` raises instead of
        hanging out a timeout), and make future ``submit()`` raise so
        the server answers with an error. Idempotent: the first
        failure wins (the clean-stop drain must not overwrite a device
        error) and re-finishing an already-finished request is a no-op
        for its consumers."""
        if self.failure is None:
            self.failure = exc
        # what was settled was served: its clients get it, and a request
        # that ended with it finishes as it would have
        self._publish()
        self._admitting = None  # its request is failed via _slot_req
        # the span that raised (a phase of the turn, or the admission's
        # request) carries status ``error``; every other request names
        # that turn's trace instead of being kept as an error of its own
        turn = tracing.current()
        failed_by = turn.trace_id if turn is not None else ""

        def fail(req: _Request) -> None:
            if req.finish_t is None:
                req.error, req.failed_by = exc, failed_by
            req._finish()

        for slot, req in enumerate(self._slot_req):
            if req is not None:
                fail(req)
                self._slot_req[slot] = None
        while self._held:
            fail(self._held.popleft())
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                fail(req)

    def _fail_admission(self, req: _Request, exc: Exception) -> None:
        """An admission raised: its ``engine.admit`` span closes with
        status ``error``, its request fails first, then the engine."""
        tracing.set_status("error", f"{type(exc).__name__}: {exc}")
        req.error = exc
        req._finish()
        self._fail_engine(exc)

    def _loop(self) -> None:
        try:
            if self._mesh is not None:
                # the mesh context is thread-local: the loop thread
                # (where every jit compiles and runs) must enter it
                with jax.set_mesh(self._mesh):
                    self._run_loop()
            else:
                self._run_loop()
        finally:
            # drain on ANY exit (stop sentinel, device failure, bug):
            # the loop thread owns _slot_req, so draining here — never
            # from stop()'s caller thread — cannot race an in-flight
            # decode chunk still emitting into the same requests
            self._fail_engine(RuntimeError("decode engine stopped"))

    def _run_loop(self) -> None:
        while not self._stopped:
            if (
                self._admitting is None
                and not self._held
                and all(r is None for r in self._slot_req)
                and self._queue.empty()
                and self._settled is None  # else: a turn, to publish it
            ):
                # nothing in flight, nothing queued: one span per idle
                # period, a root of its own (never "slow"), so that an
                # idle engine neither writes a turn every poll nor has
                # its wait kept as a stalled turn
                with hot_span("engine.idle"):
                    while not (
                        self._wake.wait(timeout=0.05)
                        or self._stopped
                        or not self._queue.empty()
                    ):
                        pass
                    self._wake.clear()
                continue
            self.turns += 1
            with hot_span("engine.turn", turn=self.turns):
                go_on = self._turn()
                self._close_turn()
                if not go_on:
                    return

    def _turn(self) -> bool:
        """One turn of the loop: admit; then, if anything decodes,
        dispatch a chunk; publish the LAST chunk's tokens and dispatch
        the next part of an admission in parts behind the chunk; fetch
        and emit the first token of each prefill that has not streamed
        its own yet; fetch the chunk; settle its tokens. Each phase is a
        child span of the caller's ``engine.turn`` and they tile it; a
        phase that fails closes with status ``error``. False: the loop
        must exit (stop sentinel, or the engine failed).

        What goes out before the chunk's fetch is what needs nothing of
        it. A part of an admission needs its prompt, its private cache
        and its offset, all known before the chunk was dispatched, so it
        is queued behind the chunk and the device walks from one into
        the other with no host in between (``engine.admit`` with
        ``ahead=1``; it is the coming turn's one part, and the top of
        that turn runs none: on the device the order stays part, whole
        prompts, chunk). Publishing the last chunk's tokens (stamps,
        streams, histograms, finished requests' traces: ``engine.emit``
        with ``deferred=1``) needs nothing of the device at all, so it
        waits for this turn's dispatches and then runs under them. The
        NEXT decode chunk does need this one's tokens: they say which
        slots ended and are free for the arrivals waiting, so no chunk
        is ever in flight across a turn's end, and at every such end
        the slots hold exactly the tokens their requests do."""
        adm = self._admitting
        went_ahead, self._part_ahead = self._part_ahead, False
        with hot_span("engine.admit", **self._parts_attr()):
            if adm is not None and (not went_ahead or adm["req"].cancelled):
                # one prefill part per loop turn: active slots get a
                # decode chunk below before the next part runs. A part
                # that went out behind the last chunk WAS this turn's
                # (its request, if cancelled since, is dropped here)
                req = adm["req"]
                try:
                    self._admit_step()
                except Exception as e:  # noqa: BLE001 — state integrity unknown
                    self._fail_admission(req, e)
                    return False
            while None in self._slot_req:
                if self._admitting is None and self._held:
                    req = self._held.popleft()
                else:
                    try:
                        req = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if req is None:
                        return False
                if req.cancelled:
                    # client left while the request was still queued:
                    # don't spend a prefill (possibly a fresh compile)
                    # on it
                    req._finish()
                    continue
                in_parts = (
                    self.prefill_chunk is not None
                    and len(req.prompt) > self.prefill_chunk
                )
                if in_parts and self._admitting is not None:
                    req.held_t = time.monotonic()
                    self._held.append(req)  # the lane is taken
                    continue
                req.admit_t = time.monotonic()
                self.m_queue_wait.observe(req.admit_t - req.submit_t)
                try:
                    if in_parts:
                        self._begin_chunked_admit(req)
                    else:
                        self._admit(req)
                except Exception as e:  # noqa: BLE001 — state integrity unknown
                    self._fail_admission(req, e)
                    return False
            self.m_queue_depth.set(self._queue.qsize() + len(self._held))
            self._charge_waits()
        # the slots this turn's chunk decodes, beside the one an
        # admission in parts holds
        admitting = int(self._admitting is not None)
        decoding = sum(r is not None for r in self._slot_req) - admitting
        if not decoding:
            # nothing decoding: a chunked admission runs its parts
            # back-to-back, one turn each, at the top
            self._publish_in_turn()
            return True
        # two compiled chunk programs: the greedy one (argmax alone)
        # whenever every in-flight request is greedy, else the general
        # sampler
        all_greedy = all(
            r is None or r.temperature <= 0 for r in self._slot_req
        )
        if self._spec_fn is not None:
            # draft attached (greedy-only by submit contract):
            # spec_rounds_per_call rounds per loop turn
            program, chunk_fn = "spec", self._spec_fn
            weights = (self.params, self.lora, self.draft_params)
        else:
            program = "greedy" if all_greedy else "sample"
            chunk_fn = (
                self._decode_greedy_fn if all_greedy else self._decode_fn
            )
            weights = (self.params, self.lora)
        pending, self._pending_first = self._pending_first, []
        try:
            with hot_span("engine.dispatch", program=program):
                self._state, (toks, mask) = chunk_fn(weights, self._state)
                chunk_stats = self._take_chunk_stats()
        except Exception as e:  # noqa: BLE001 — state integrity unknown
            self._fail_engine(e)
            return False
        # the device has the turn's prefills and its chunk queued: what
        # the host still owes from the last turn runs under them
        self._publish_in_turn()
        if self._admitting is not None:
            with hot_span("engine.admit", ahead=1, **self._parts_attr()):
                req = self._admitting["req"]
                parts = self.parts
                try:
                    self._admit_step()
                except Exception as e:  # noqa: BLE001 — state integrity unknown
                    self._fail_admission(req, e)
                    return False
                # a cancelled request's step dispatched nothing
                self._part_ahead = self.parts > parts
                self.parts_ahead += self.parts - parts
        try:
            for req, first, slot in pending:
                if self._slot_req[slot] is not req:
                    # the final part went out behind the last chunk and
                    # its request was cancelled before this fetch: the
                    # slot is no longer its own
                    continue
                # the device runs its programs in order: this token
                # exists when the request's own prefill ends, with the
                # turn's later prefills and the chunk running behind
                # it. The host waits for that and no longer, and
                # streams it
                with hot_span("engine.fetch", first_tokens=1):
                    tok = int(jax.device_get(first))
                with hot_span("engine.emit", first_tokens=1):
                    self._emit_first(req, tok, slot)
            # the host waiting for the device: the chunk's tokens
            with hot_span("engine.fetch"):
                toks, mask, stats = jax.device_get((toks, mask, chunk_stats))
        except Exception as e:  # noqa: BLE001 — state integrity unknown
            self._fail_engine(e)
            return False
        with hot_span("engine.emit"):
            if "moe_stats" in stats:
                moe = stats["moe_stats"]
                self.moe_local_assignments += int(moe[0])
                self.moe_experts_hit += int(moe[1])
                self.moe_dropped += int(moe[2])
                self.moe_rows_computed += int(moe[3])
            if "sel_stats" in stats:
                self.sel_causal_rows += int(stats["sel_stats"][0])
                self.sel_attended_rows += int(stats["sel_stats"][1])
            if self._window_layers and self._spec_fn is None:
                self._count_window_blocks_skipped(mask)
            self._settle_chunk(toks, mask)
            if self._spec_fn is None:
                self._count_slot_steps(mask, decoding, admitting)
            if program == "sample":
                self.decode_calls_sampled += 1
        return True

    def _charge_waits(self) -> None:
        """The end of a turn's top admit phase: the time since the last
        one is charged once for every request that still waits, to the
        lane where a slot is free (only ``_held`` can then hold any: the
        phase drains the queue while one is) and to the slots where none
        is. Two products, no walk over the queue; an arrival from here
        on waits a turn and shows in its own ``queued`` span."""
        now = time.monotonic()
        since, self._wait_stamp = now - self._wait_stamp, now
        held = len(self._held)
        if None in self._slot_req:
            self.wait_lane_s += held * since
        else:
            self.wait_slot_s += (held + self._queue.qsize()) * since

    def _count_slot_steps(self, mask, decoding: int, admitting: int) -> None:
        """Every slot-step of the chunk just fetched, under one of
        ``SLOT_STATES``: ``decoding`` slots held a decoding request at
        its dispatch and ``admitting`` (0 or 1) the admission in parts;
        the fetched mask says in which steps a slot emitted. A free
        slot stood empty for the lane if anything sat in ``_held`` when
        the turn's top admit phase ended (only that phase moves
        ``_held``, so it reads the same here), else for want of work.
        The registry's counter moves with it: once a chunk, so once a
        turn."""
        k = mask.shape[1]
        live = int(mask.sum())
        free = k * (self.n_slots - decoding - admitting)
        lane = bool(self._held)
        chunk = (
            live, k * decoding - live, k * admitting,
            free if lane else 0, 0 if lane else free,
        )
        for state, n in zip(SLOT_STATES, chunk):
            self.slot_steps[state] += n
            self.m_slot_steps.inc({"state": state}, n)

    def _turn_totals(self) -> dict:
        """The loop's running totals, as an ``engine.turn`` span carries
        them: CUMULATIVE, so that two turns give any interval's counts
        by their difference, whatever a caller did or did not snapshot;
        ``held`` alone is a depth."""
        totals = {
            **{f"slot_steps_{s}": n for s, n in self.slot_steps.items()},
            "wait_lane_s": self.wait_lane_s, "wait_slot_s": self.wait_slot_s,
            "held": len(self._held),
            "parts": self.parts, "parts_ahead": self.parts_ahead,
            "prefill_tokens": self.prefill_tokens,
            "prefill_positions": self.prefill_positions,
        }
        if "sel_stats" in self._stats_spare:
            totals.update(
                sel_causal_rows=self.sel_causal_rows,
                sel_attended_rows=self.sel_attended_rows,
                prefill_pairs=self.prefill_pairs,
                prefill_pairs_first=self.prefill_pairs_first,
            )
        return totals

    def _close_turn(self) -> None:
        """Once a turn, as its span closes: the registry's view of the
        lane moves and the span takes the totals as attributes. Not
        beside a draft (no ledger is kept)."""
        if self._spec_fn is None:
            self.m_lane_held.set(len(self._held))
            tracing.set_attrs(**self._turn_totals())

    def _parts_attr(self) -> dict:
        """For an ``engine.admit`` span while an admission in parts is
        under way: how many parts it takes in all."""
        adm = self._admitting
        return {} if adm is None else {
            "parts": -(-len(adm["req"].prompt) // self.prefill_chunk),
        }

    def _take_chunk_stats(self):
        """The counters of the chunk just dispatched (``CALL_COUNTERS``:
        device values by name, those the cache keeps), taken OUT of the
        state and spares put in their place. A final part dispatched behind
        the chunk donates the state, and with it every buffer the state
        holds: the counters the host is about to fetch must not be among
        them. The spare is the buffer the last chunk's counters came in,
        fetched a turn ago; what it holds is never read (the next chunk
        zeroes its counters and the prefill programs hand them through).
        Not beside a draft: its rounds add to the counters they find,
        and no part is ever dispatched beside them (``submit``)."""
        cache = self._state["cache"]
        stats = {name: cache[name] for name in self._stats_spare}
        if self._spec_fn is None:
            cache.update(self._stats_spare)
            self._stats_spare = stats
        return stats

    def _count_window_blocks_skipped(self, mask) -> None:
        """kv-blocks that lie wholly before a window layer's window and
        are therefore neither fetched nor computed (``live_range`` of
        ``ops/pallas_decode_attention.py``), over the chunk's steps, the
        slots that decoded in them and the window layers: from the
        positions the host already knows."""
        from odh_kubeflow_tpu.ops import pallas_decode_attention as pda

        cache = self._state["cache"]
        block_k = pda.block_k_for(cache["wk"])
        for slot, req in enumerate(self._slot_req):
            if req is None or not mask[slot].any():
                continue
            # the first step's query: the newest token (a prefill's
            # first token was emitted ahead of this chunk's fetch)
            pos = len(req.prompt) + len(req.tokens) - 1
            for step in range(int(mask[slot].sum())):
                for window, layers in self._window_layers.items():
                    self.window_blocks_skipped += layers * (
                        max(pos + step - window + 1, 0) // block_k
                    )

    def _emit_first(self, req: _Request, tok: int, slot: int) -> None:
        """Stream the first token of a prefill this turn admitted,
        ahead of the fetch of the chunk dispatched behind it."""
        req._emit(tok)
        self._observe_emit(req)
        self.tokens_emitted += 1
        self.first_tokens_early += 1
        if tok == req.eos_id:
            # the prefill itself left the slot inactive on the device
            # (``_write_slot_state``): the chunk behind it emits
            # nothing for it, and the host frees it here
            req._finish()
            self._slot_req[slot] = None

    def _settle_chunk(self, toks, mask) -> None:
        """What the next admit phase and the counters' readers depend
        on, right after the chunk's fetch: each slot's live tokens are
        taken for their request, a request they complete (or a cancelled
        one) gives up its slot, the step and token counts move. The
        tokens themselves, with their stamps, streams and everything
        else a client sees, wait in ``_settled`` for ``_publish``, where
        each goes through ``_Request._emit`` as a first token does: at
        most one chunk's tokens, for the length of one admit and
        dispatch, and always published before the next chunk is
        settled, so ``req.tokens`` here is all that came before."""
        self.decode_calls += 1
        self.decode_steps += (
            self.spec_rounds_per_call
            if self._spec_fn is not None
            else self.chunk
        )
        settled = []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if (
                self._admitting is not None
                and self._admitting["slot"] == slot
            ):
                # mid-admission slot: device-inactive, no
                # emissions; cancellation is _admit_step's job
                # (freeing it here would race a re-claim)
                continue
            if req.cancelled:
                # client abandoned the stream: deactivate the slot
                # on device (stops its kv growth and emission) and
                # free it now instead of decoding for nobody
                self._state["active"] = (
                    self._state["active"].at[slot].set(False)
                )
                self._slot_req[slot] = None
                settled.append((req, (), True))
                continue
            live = toks[slot][mask[slot]].tolist()
            self.tokens_emitted += len(live)
            ended = req.completed_by(live)
            if ended:
                self._slot_req[slot] = None
            if live or ended:
                settled.append((req, live, ended))
        held = sum(1 for r in self._slot_req if r is not None)
        self._settled = (settled, held)

    def _publish_in_turn(self) -> None:
        """``_publish`` as a phase of the turn under way, where there
        is something to publish."""
        if self._settled is not None:
            with hot_span("engine.emit", deferred=1):
                self._publish()

    def _publish(self) -> None:
        """The last chunk's settled tokens as their clients see them:
        appended to their requests, stamped now, put on their streams,
        observed, and the requests that ended with them finished (done,
        trace written). Called once
        the turn's programs have been dispatched, where a turn finds
        nothing to dispatch, and before the engine fails or stops, so
        nothing settled is ever left unpublished."""
        if self._settled is None:
            return
        (settled, held), self._settled = self._settled, None
        for req, live, ended in settled:
            for tok in live:
                req._emit(tok)
                self._observe_emit(req)
            if ended:
                req._finish()
        self.m_occupancy.set(held / float(self.n_slots))

    # -- public API ---------------------------------------------------------

    def submit(
        self,
        prompt: list[int],
        *,
        max_tokens: int = 64,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        eos_id: Optional[int] = None,
        stream: bool = False,
    ) -> _Request:
        if self.failure is not None:
            raise RuntimeError(
                f"decode engine is down: {self.failure!r}"
            )
        if not prompt:
            raise ValueError("empty prompt")
        if self.draft_params is not None and temperature > 0:
            raise ValueError(
                "draft-enabled engine decodes greedily (speculative "
                "verify is exact only under argmax); use the one-shot "
                "sampling path for temperature > 0"
            )
        chunkable = (
            self.prefill_chunk is not None
            and len(prompt) > self.prefill_chunk
            # the draft prefill still needs a full-prompt bucket
            and self.draft_params is None
        )
        if not chunkable and len(prompt) > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt longer than max bucket {self.prompt_buckets[-1]}"
            )
        headroom = self.spec_k if self.draft_params is not None else 0
        if len(prompt) + max_tokens + headroom > self.max_len:
            # the speculative verify may write up to spec_k slots past
            # the final kept token — the cache needs that scratch tail
            raise ValueError(
                f"prompt+max_tokens (+{headroom} speculative headroom) "
                f"exceeds engine max_len {self.max_len}"
            )
        req = _Request(
            prompt=list(prompt),
            max_tokens=max_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            eos_id=-1 if eos_id is None else int(eos_id),
            token_q=queue.Queue() if stream else None,
            submit_t=time.monotonic(),
            submit_wall=time.time(),
        )
        self._queue.put(req)
        self.m_queue_depth.set(self._queue.qsize())
        self._wake.set()
        # the loop thread may have exited (stop() or a device failure)
        # between the pre-check above and the put — its final drain
        # would then never see this request and result() would hang to
        # its timeout. Re-check and fail the request ourselves; _finish
        # is idempotent so double-draining with the loop is safe.
        if self.failure is not None or self._stopped:
            err = self.failure or RuntimeError("decode engine stopped")
            saw_sentinel = False
            try:
                while True:
                    q = self._queue.get_nowait()
                    if q is None:
                        # stop()'s shutdown sentinel — remember it and
                        # keep draining: our request may sit behind it
                        # with no live loop left to drain it
                        saw_sentinel = True
                        continue
                    # only requests we drained ourselves are provably
                    # un-admitted; one the loop already took may be
                    # completing concurrently and must not get a late
                    # error write (its drain is the loop's job). Every
                    # drained request is finished — dropping one here
                    # would strand its result() to the timeout.
                    if q.error is None:
                        q.error = err
                    q._finish()
            except queue.Empty:
                pass
            if saw_sentinel:
                # restore it so a still-live loop's early-exit fires
                self._queue.put(None)
        return req

    def stop(self) -> None:
        """Signal the loop to exit and wait for it. The loop itself
        drains in-flight requests on exit (see _loop's finally) — the
        drain must run on the loop thread, after any in-flight decode
        chunk finished, or it would race the chunk's emissions. A
        cold-compile chunk can exceed the join timeout; the daemon
        thread still drains when it completes."""
        self._stopped = True
        self._queue.put(None)
        self._wake.set()
        self._thread.join(timeout=60)

    def slot_state(self, slot: int, kind: str = STATE) -> dict:
        """Row ``slot`` of every stack of ``kind`` (the recurrent
        state's where not said; ``INDEXED``: a stream's keys, values and
        indexer keys), on the host: ``{name: [layers, ...]}``. Of a
        STOPPED engine only
        (while the loop runs it owns the buffers, which its programs
        donate): what the slot's stream has left behind it, its prompt
        and every token emitted but the last, which the next step would
        have taken in."""
        assert not self._thread.is_alive(), "stop the engine first"
        return {
            name: np.asarray(leaf[:, slot])
            for name, leaf in self._state["cache"].items()
            if stack_kind(name) == kind
        }
