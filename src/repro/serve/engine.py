"""Slot-based continuous-batching serving engine (arena or paged KV).

The paper's core argument (arXiv 2202.03263) is that asynchrony wins
wall-clock time: fast participants proceed instead of convoying behind
slow ones.  Wave batching violates that on the serving side — a wave
decodes until its *longest* generation finishes, so one long request
convoys every short one.  This engine is the serving-side analogue of
API-BCD's asynchrony:

  * a fixed batch of `max_batch` decode rows, ONE persistent jitted
    decode step over all of them — dead rows are masked host-side and
    recycled, so there are no recompiles as the batch composition
    churns,
  * an **admission scheduler** that prefills a queued request into any
    freed row *between* decode steps while the other rows keep
    decoding,
  * two KV storage modes behind the same submit/step/run API:

    **arena** (default): each row owns a full capacity-T cache row
    (power-of-two bucketed), so a request is bounded by
    `plen + max_new_tokens <= capacity` and memory scales with the
    worst case whether or not the tokens ever exist.

    **paged** (`paged=True`): all rows share one pool of fixed-size KV
    blocks (`models.transformer.init_pool`) with host-side per-row
    block tables (`repro.serve.paging`).  Blocks are allocated on
    demand as decode crosses block boundaries and freed the moment a
    request finishes, so memory scales with *live* tokens; admission is
    gated on free blocks, not free full-length rows, and generations
    are bounded by the pool, not a per-slot capacity.  Long prompts
    stream in through fixed-size **chunked prefill** (one compile)
    instead of one padded batch-1 launch.  Paged mode covers
    attention-family stacks (GQA and MLA share the code path), and
    sliding-window GQA pages as a block **ring** — a slot holds at most
    ceil(window / block_size) blocks, position p lives at ring slot
    p % window, eviction is overwrite, and a full-ring generation
    allocates zero further blocks however long it runs.  The engine
    auto-selects the arena for recurrent state (no pages to page) and
    windowed MLA (the arena mla_prefill ignores the window, so no
    windowed-MLA family exists to stay bit-identical with).

Paged admission comes in two policies (`preemption=`):

    **"recompute"** (default): vLLM-style preempt-and-recompute.
    Admission is optimistic — a request is admitted when the blocks
    that are free *right now* cover its prompt (plus a one-block
    watermark), not its worst case.  When a decode step crosses a block
    boundary and the pool is empty, the scheduler preempts the newest
    admission (LIFO — the oldest running request is never evicted while
    a younger one holds blocks), frees its blocks, and re-queues it in
    uid position — ahead of every never-admitted request, so the queue
    stays uid-sorted — for recompute: on re-admission its prompt streams back
    in through the same chunked-prefill path (bit-identical to its
    original admission — same chunks, same offsets), and its
    generated-so-far tokens *replay* through the shared decode step,
    one per step, logits discarded (each successor is already known).
    Replay rides the same batched launches the live rows are decoding
    in — recompute adds no extra device launches beyond the prompt
    chunks — and because every position is rebuilt by the same kernel
    that wrote it originally, the restored KV and decode state are
    bit-for-bit the state of an uninterrupted run: the final output is
    bitwise unchanged even where logits tie exactly.  (Re-prefilling
    the generated tokens instead would be mathematically identical but
    chunk-batched forwards round differently at the ULP level, which
    flips exact ties.)  Every request still completes (the oldest
    running request only grows), it just may pay recompute steps.

    **"reserve"**: pessimistic worst-case reservation — admission
    requires `available >= worst_case_blocks`, so a mid-generation
    alloc can never fail and nothing is ever preempted; workloads that
    EOS early (or simply haven't grown yet) leave reserved blocks idle.

Greedy decode is row-independent (no cross-batch ops in the model), so
a request admitted into a half-full decode batch produces bit-identical
output to the same request served alone — batching, admission timing,
preemption, and the arena/paged storage choice are all semantically
inert (tests/test_server.py asserts this).

The host loop is built not to convoy behind the device (or, on a
multi-process mesh, behind the slowest host — the straggler problem the
paper is about):

  * every jitted step is **token-returning**: greedy argmax runs inside
    the jit and the per-decode-step device→host transfer is `[B]` int32
    token ids, never `[B, 1, vocab]` logits (on a mesh the vocab dim is
    model-sharded, so a logits fetch would be a cross-host gather every
    step);
  * admission launches a whole round of prefills back-to-back and only
    then resolves their first tokens — no per-admission blocking sync
    between launches;
  * block tables / lengths / current tokens live in **device mirrors**:
    the decode step returns advanced lengths and next tokens, which
    feed straight back in, so steady-state decoding performs zero
    host→device uploads (mirrors re-sync from host state only when
    admission, finish, or preemption actually changes it);
  * with `overlap=True` (default where the family supports it),
    **admission overlaps decode** instead of serializing in front of
    it.  The queue head's prefill rides the decode launches the live
    rows were paying for anyway — a **unified mixed step** (the
    Sarathi/vLLM mixed batch: decode all rows + one prefill unit per
    launch, `Model.mixed_step_tokens` / `mixed_step_paged_tokens`) —
    and, on the paged backend, any further admissible requests launch
    their prefills asynchronously in the same scheduler pass, with NO
    first-token resolution before the decode dispatch (the arena
    admits through the mixed step only: its decode ring-inserts at a
    cache-carried per-slot ptr, so a dead arena slot stops being
    write-inert the moment a staged prefill fills its row — see
    models/attention.py).  Staged slots stay dead to
    decode (zero validity length / zeroed table row, so the fused
    decode's writes for them are inert) until `_resolve_staged`
    installs them at the start of a later step, when the blocking
    fetch is free — the prior step's token fetch already synced past
    the producing launch.  All admissions staged while one stream is
    in flight resolve *together* once it lands, oldest first, so no
    request ever starts decoding before an older one and FIFO
    completion order survives the overlap.  Overlapped output is
    bitwise identical to the serialized scheduler: greedy decode is
    row-independent, the prefill subgraph inside the mixed step sees
    exactly the operands a standalone launch would, and the mixed
    trace runs decode before prefill so the dead slot's garbage decode
    write is fully overwritten before the slot ever becomes valid.
    On meshes with two or more nontrivial axes the engine swaps the
    mixed launch for **async composition** — the serialized scheduler's
    own decode and prefill graphs dispatched back-to-back without
    blocking — because XLA SPMD rounds the fused graph's dense ops
    context-dependently there (see `overlap_mode` on the constructor).

`Engine.stats` reports the split (admission host time vs prefill wait
vs decode dispatch vs token fetch, upload/fetch counts, mixed-step and
overlapped-admission counters, preemptions);
`benchmarks/bench_mesh_serving.py` records it from a real 2-process
run, including a Poisson-arrival arm comparing the two schedulers.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from collections import deque
from typing import Deque, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.bucketing import bucket_length, chunks_needed, table_width
from repro.serve.paging import BlockAllocator, blocks_needed
from repro.utils.hotpath import hot_loop

_PREFILL_FLOOR = 8      # smallest prompt bucket (keeps compile count tiny)
_ADMIT_WATERMARK = 1    # spare blocks optimistic admission leaves free


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: Optional[int] = None
    output: Optional[np.ndarray] = None
    # preempt-and-recompute bookkeeping: tokens generated before the
    # request was last evicted.  On re-admission they replay through
    # the decode step to rebuild the KV bit-for-bit, and they are
    # prepended to the final output; `prompt` and `max_new_tokens`
    # keep their user-facing values throughout.
    gen_prefix: List[int] = dataclasses.field(default_factory=list)
    preemptions: int = 0


def _min_ring(arena_shapes) -> float:
    """Smallest ring-buffer capacity across attention cache leaves
    ([layers, B, T, ...]); inf when the model has none."""
    caps = []

    def visit(path, leaf):
        name = None
        for k in reversed(path):
            if hasattr(k, "key"):
                name = k.key
                break
        if name in ("k", "v", "ckv", "kpe"):
            caps.append(leaf.shape[2])
        return leaf

    jax.tree_util.tree_map_with_path(visit, arena_shapes)
    return min(caps) if caps else float("inf")


@dataclasses.dataclass(frozen=True)
class FamilyCaps:
    """Per-family serving capabilities, probed from the model.

    Replaces the old monolithic fallback chain in Engine.__init__ with
    piecewise flags, so recurrent / sliding-window / MoE stacks opt in
    (or out) per capability instead of hitting one table:

      pad_prompts: prompt padding to pow2 buckets is semantically inert
        (pure-attention stack with full-capacity rings).  Recurrent
        layers fold padding into their state, moe routing capacity
        depends on the static sequence length, and sliding-window rings
        would let pads evict real context — those prefill at exact
        lengths.
      supports_paging: the shared block-pool KV backend works (all-attn
        stack and init_pool accepts the family — recurrent state has no
        pages to page).  Sliding-window GQA pages as a fixed block RING
        (position p at ring slot p % window — eviction is overwrite);
        windowed MLA has no windowed arena family to stay bit-identical
        with and keeps the arena.
      supports_chunked_prefill: prompts can stream in through fixed
        chunks (the paged admission path; rides the same predicate).
      supports_mixed_step: the unified decode+prefill launch is sound —
        requires a row-independent decode over a dead slot whose fused
        prefill writes it cannot corrupt: prompt padding (pad_prompts)
        gives the arena that, null-block table routing gives the pool
        that (supports_paging); either predicate plus the model's mixed
        entry points unlocks the step.  The Engine additionally gates
        overlap on the backend it resolved to — a windowed stack that
        fell back to the ARENA stays serialized (its arena prefill
        cannot pad, so the fused arena step has no compiled shape for
        it), while the same stack paged gets the full overlap path.
    """
    pad_prompts: bool
    supports_paging: bool
    supports_chunked_prefill: bool
    supports_mixed_step: bool


# probe_family_caps memo: eval_shape-tracing every entry point per Engine
# construction is pure overhead when engines share a model (the
# BatchedServer shim builds one per cache bucket).  Weakly keyed by the
# Model exactly like _JIT_CACHE below; the inner key is the probe's
# remaining signature.
_CAPS_CACHE = weakref.WeakKeyDictionary()


def probe_family_caps(model, *, max_batch: int = 1, capacity: int = 256,
                      cache_dtype=jnp.bfloat16) -> FamilyCaps:
    """Probe what the serving engine may do with `model` (abstractly —
    eval_shape only, no allocation; memoized per model).  `capacity`
    matters: a window override baked into the model caps its rings
    below a large enough capacity, which disables padding.  A windowed
    GQA init_pool accepts (the pool pages the window as a block ring);
    windowed MLA raises, disabling paging."""
    per_model = _CAPS_CACHE.setdefault(model, {})
    key = (int(max_batch), int(capacity), jnp.dtype(cache_dtype).name)
    if key not in per_model:
        per_model[key] = _probe_family_caps(model, max_batch, capacity,
                                            cache_dtype)
    return per_model[key]


def _probe_family_caps(model, max_batch, capacity, cache_dtype) -> FamilyCaps:
    if model.prefill_into_slot is None:
        return FamilyCaps(False, False, False, False)
    all_attn = all(t == "attn" for t in model.cfg.layer_types)
    arena_shapes = jax.eval_shape(
        lambda: model.init_arena(max_batch, capacity, dtype=cache_dtype))
    pad_prompts = all_attn and _min_ring(arena_shapes) >= capacity
    paging = False
    if model.init_pool is not None and all_attn:
        try:
            jax.eval_shape(lambda: model.init_pool(1, 2, dtype=cache_dtype))
            paging = True
        except NotImplementedError:
            pass
    # the mixed step needs a dead slot the fused prefill fully
    # overwrites: prompt padding gives the arena that (pad_prompts), the
    # null-block table routing gives the pool that (paging) — either
    # backend being sound unlocks the entry points; the Engine still
    # gates overlap on the backend it actually resolved to
    mixed = bool((pad_prompts or paging)
                 and model.mixed_step_tokens is not None
                 and model.mixed_step_paged_tokens is not None)
    return FamilyCaps(pad_prompts=pad_prompts, supports_paging=paging,
                      supports_chunked_prefill=paging,
                      supports_mixed_step=mixed)


# One jit wrapper per (model, entry point): engines over the same model
# share traces/executables, so a fresh Engine (e.g. one per cache bucket
# in the BatchedServer shim) costs no recompilation.  Weakly keyed by
# the Model so wrappers + executables die with it (the model's entry
# lambdas close over cfg, not the Model, so no cycle pins the key).
_JIT_CACHE = weakref.WeakKeyDictionary()


def _shared_jit(model, name, donate_argnums=()):
    per_model = _JIT_CACHE.setdefault(model, {})
    key = (name, donate_argnums)
    if key not in per_model:
        # repro-lint: disable=recompile-hazard -- key space is (entry-point
        # name, donation flag): a handful of entries per model, bounded
        per_model[key] = jax.jit(getattr(model, name),
                                 donate_argnums=donate_argnums)
    return per_model[key]


class Engine:
    """Continuous-batching greedy-decode engine over one model + params.

    API: submit(prompt, max_new_tokens, eos_id) -> uid;
    step() -> requests finished by this step; run() -> drain the queue.

    paged=True requests the block-pool KV backend (see module
    docstring); the engine falls back to the arena when the model
    cannot page (`engine.paged` reports the resolved mode).
    block_size / num_blocks / prefill_chunk size the pool (defaults:
    the arena's footprint, i.e. max_batch * capacity tokens of blocks).
    preemption picks the paged admission policy — "recompute"
    (optimistic, preempt-and-recompute under pressure; default) or
    "reserve" (pessimistic worst-case reservation, never preempts);
    the arena never preempts either way (a slot is a full reservation).

    overlap_mode picks HOW overlapped admission shares the step budget:
    "fused" runs the unified mixed launch (decode rows + the stream's
    prefill unit in ONE jit — dense ops shared, collectives halved);
    "async" dispatches the SAME decode and prefill graphs the
    serialized scheduler uses, back-to-back without blocking on
    first-token resolution.  "auto" (default) resolves to "fused"
    except on meshes with a nontrivial data axis, for two independent
    reasons.  Perf: the mixed batch is token-concatenated — shape
    [1, B+S, D], batch dim 1 — so a data axis has nothing to shard and
    the whole mixed launch replicates onto every data shard (measured
    2.5x slower than serialized on a data-only mesh), whereas on pure
    model-parallel meshes the fused launch SHARES the per-layer
    collectives between decode and prefill and admission becomes
    nearly free.  Bitwise: on data x model meshes XLA SPMD compiles
    the fused graph's dense ops with context-dependent ULP rounding
    (measured on CPU) and would break the serialized-vs-overlapped
    digest gate; "async" keeps that gate by construction — identical
    compiled graphs, identical operands, only the host-side blocking
    removed.
    """

    def __init__(self, model, params, *, max_batch: int = 8,
                 max_len: int = 256, cache_dtype=jnp.bfloat16, mesh=None,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 preemption: str = "recompute", overlap: bool = True,
                 overlap_mode: str = "auto"):
        if preemption not in ("recompute", "reserve"):
            raise ValueError(
                f"preemption must be 'recompute' or 'reserve', "
                f"got {preemption!r}")
        if overlap_mode not in ("auto", "fused", "async"):
            raise ValueError(
                f"overlap_mode must be 'auto', 'fused' or 'async', "
                f"got {overlap_mode!r}")
        self.preemption = preemption
        self.num_preemptions = 0    # total evictions (observability)
        if model.prefill_into_slot is None:
            raise NotImplementedError(
                f"family {model.cfg.family!r} has no slot-arena entry points")
        self.model = model
        self.params = params
        self.max_batch = int(max_batch)
        self.capacity = bucket_length(max_len)
        # per-family capabilities (padding / paging / mixed-step), probed
        # piecewise: a family that cannot page can still pad, one that
        # cannot do either still serves through the serialized arena path
        self.caps = probe_family_caps(model, max_batch=self.max_batch,
                                      capacity=self.capacity,
                                      cache_dtype=cache_dtype)
        self._pad_prompts = self.caps.pad_prompts
        self.paged = bool(paged and self.caps.supports_paging)
        # effective sliding window (0 = full causal): sizes ring tables,
        # block reservations and width buckets on the paged backend
        self.window = int(model.window or 0)
        # overlapped admission needs the unified mixed step AND a
        # backend whose dead slots survive a fused prefill: the pool
        # always qualifies (null-block routing), the arena only when it
        # can pad prompts — a windowed ARENA engine stays serialized
        # (exact behavior of overlap=False), a windowed PAGED engine
        # overlaps
        self.overlap = bool(overlap and self.caps.supports_mixed_step
                            and (self.paged or self.caps.pad_prompts))
        if overlap_mode == "auto":
            # a nontrivial data axis rules fused out twice over: the
            # [1, B+S, D] mixed batch gives it nothing to shard (the
            # launch replicates), and combined with a model axis the
            # fused graph loses bitwise equality (see class docstring)
            data_sharded = (mesh is not None
                            and int(mesh.shape.get("data", 1)) > 1)
            overlap_mode = "async" if data_sharded else "fused"
        # resolved strategy (see class docstring); meaningless without
        # overlap, so report "" there
        self.overlap_mode = overlap_mode if self.overlap else ""
        self.prefill_shapes: set = set()    # admitted Sp values (observability)

        arena_shapes = jax.eval_shape(
            lambda: model.init_arena(self.max_batch, self.capacity,
                                     dtype=cache_dtype))

        self._repl = None   # replicated sharding for mirrors (mesh only)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl = NamedSharding(mesh, PartitionSpec())
        self._mixed = None
        if self.paged:
            self.block_size = int(block_size)
            self.num_blocks = int(
                num_blocks if num_blocks is not None
                else max(1, self.max_batch * self.capacity
                         // self.block_size))
            self.prefill_chunk = int(prefill_chunk)
            if self.window:
                # a chunk wider than the ring would scatter two of its
                # positions into the same ring slot in one launch
                # (unspecified scatter winner — the later position must
                # survive, and only chunk <= window guarantees it)
                self.prefill_chunk = min(self.prefill_chunk, self.window)
            self._allocator = BlockAllocator(self.num_blocks)
            # one table row per decode slot; the full width lets a
            # single request, at the limit, use every pool block — but
            # the jitted steps only ever see a power-of-two slice wide
            # enough for the live maximum (_table_width), so per-step
            # attention work scales with live tokens, not pool size,
            # at O(log num_blocks) compiles
            self._tables = np.zeros((self.max_batch, self.num_blocks),
                                    np.int32)
            self._slot_reserved = [0] * self.max_batch
            if mesh is not None:
                from repro.dist.serving import (
                    make_decode_rows_paged_token_step,
                    make_mixed_paged_token_step,
                    make_prefill_chunk_token_step)
                pool_shapes = jax.eval_shape(
                    lambda: model.init_pool(self.num_blocks, self.block_size,
                                            dtype=cache_dtype))
                self._prefill, (p_sh, c_sh) = make_prefill_chunk_token_step(
                    model, mesh, pool_shapes)
                self._decode, _ = make_decode_rows_paged_token_step(
                    model, mesh, self.max_batch, pool_shapes)
                if self.overlap_mode == "fused":
                    self._mixed, _ = make_mixed_paged_token_step(
                        model, mesh, self.max_batch, pool_shapes)
                self.params = jax.device_put(params, p_sh)
                # jit the init so the pool materializes directly in its
                # sharded layout — works multi-process (no cross-process
                # device_put of a host-local buffer)
                self._caches = jax.jit(
                    lambda: model.init_pool(self.num_blocks, self.block_size,
                                            dtype=cache_dtype),
                    out_shardings=c_sh)()
            else:
                self._prefill = _shared_jit(
                    model, "prefill_chunk_into_blocks_token",
                    donate_argnums=(5,))
                self._decode = _shared_jit(
                    model, "decode_rows_paged_tokens",
                    donate_argnums=(2,))
                if self.overlap_mode == "fused":
                    self._mixed = _shared_jit(
                        model, "mixed_step_paged_tokens",
                        donate_argnums=(2,))
                self._caches = model.init_pool(self.num_blocks,
                                               self.block_size,
                                               dtype=cache_dtype)
        elif mesh is not None:
            from repro.dist.serving import (make_decode_rows_token_step,
                                            make_mixed_arena_token_step,
                                            make_slot_prefill_token_step)
            self._prefill, (p_sh, c_sh) = make_slot_prefill_token_step(
                model, mesh, arena_shapes)
            self._decode, _ = make_decode_rows_token_step(
                model, mesh, self.max_batch, arena_shapes)
            if self.overlap_mode == "fused":
                self._mixed, _ = make_mixed_arena_token_step(
                    model, mesh, self.max_batch, arena_shapes)
            self.params = jax.device_put(params, p_sh)
            self._caches = jax.jit(
                lambda: model.init_arena(self.max_batch, self.capacity,
                                         dtype=cache_dtype),
                out_shardings=c_sh)()
        else:
            self._prefill = _shared_jit(model, "prefill_into_slot_token",
                                        donate_argnums=(4,))
            self._decode = _shared_jit(model, "decode_rows_tokens",
                                       donate_argnums=(2,))
            if self.overlap_mode == "fused":
                self._mixed = _shared_jit(model, "mixed_step_tokens",
                                          donate_argnums=(2,))
            self._caches = model.init_arena(self.max_batch, self.capacity,
                                            dtype=cache_dtype)

        self._queue: Deque[Request] = deque()
        self._done: List[Request] = []
        self._next_uid = 0
        self._slot_req: List[Optional[Request]] = [None] * self.max_batch
        self._gen: List[List[int]] = [[] for _ in range(self.max_batch)]
        # tokens a recomputed slot still has to re-insert through the
        # decode step before it is live again (paged "recompute" only)
        self._replay: List[Deque[int]] = [deque()
                                          for _ in range(self.max_batch)]
        # held as int32 end-to-end: these feed the jitted step directly
        # (no per-step downcast)
        self._lengths = np.zeros(self.max_batch, np.int32)  # tokens in cache
        self._cur = np.zeros(self.max_batch, np.int32)      # current token

        # device mirrors of the decode step's small operands.  The step
        # returns next tokens and advanced lengths, which feed straight
        # back in; host→device uploads happen only when host-side events
        # (admission / finish / preempt / block top-up / replay) make
        # the mirror stale — steady-state decode uploads nothing.
        self._cur_dev = None
        self._lengths_dev = None
        self._tables_dev = None
        self._tables_dev_w = -1      # width of the cached table slice
        self._cur_dirty = True
        self._lengths_dirty = True
        self._tables_dirty = True

        # overlapped-admission state.  `_stream`: the one admission whose
        # prefill rides the mixed decode launches (the queue head; one
        # chunk per step on the paged backend, the whole bucketed prompt
        # in one mixed launch on the arena).  `_staged`: admissions whose
        # prefill launches are all in flight but whose first token has
        # not been resolved — their slots stay dead to decode (zero
        # validity length / zeroed table row; paged block ids live in the
        # entry's private table until installation).
        self._stream: Optional[dict] = None
        self._staged: List[dict] = []
        self._stats = {
            "admissions": 0,         # requests prefilled into a slot
            "admit_host_s": 0.0,     # host time launching admissions
            "prefill_wait_s": 0.0,   # blocked resolving prefill tokens
            "decode_steps": 0,
            "decode_s": 0.0,         # decode launch + [B]-token fetch
            "decode_dispatch_s": 0.0,   # … its mirror-sync + launch half
            "decode_fetch_s": 0.0,      # … its blocked-on-tokens half
            "mixed_steps": 0,        # decode launches that carried a prefill
            "overlapped_admissions": 0,  # first tokens resolved deferred
                                         # (never blocked a decode dispatch)
            "topup_host_s": 0.0,     # paged block top-up / eviction work
            "replayed_tokens": 0,    # recompute replays (paged)
            "h2d_uploads": 0,        # mirror re-syncs (stale → upload)
            "decode_fetch_elems": 0,    # size of the per-step fetch …
            "decode_fetch_dtype": "",   # … proof it is [B] int32 ids
        }

    @property
    def stats(self) -> dict:
        """Per-step telemetry: admission host time vs prefill wait vs
        decode step time, mirror upload / token fetch accounting, and
        preemption counts.  `decode_fetch_elems`/`decode_fetch_dtype`
        record the actual per-decode-step device→host transfer (int32
        token ids, one per slot — never logits).  `overlap_mode` is the
        resolved overlap strategy ("fused" / "async", "" when the
        serialized scheduler is active)."""
        return dict(self._stats, preemptions=self.num_preemptions,
                    overlap_mode=self.overlap_mode)

    def _put(self, x):
        """Upload host state to a device mirror (replicated on a mesh —
        identical on every process, so multi-process engines stay in
        lockstep without communication)."""
        self._stats["h2d_uploads"] += 1
        if self._repl is not None:
            return jax.device_put(x, self._repl)
        return jax.device_put(x)

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------

    def _worst_case_blocks(self, plen: int, max_new: int) -> int:
        """Blocks a request can ever occupy: prefill writes `plen`
        entries and each decode step one more, so the cache peaks at
        plen + max_new - 1 tokens (the final token is never inserted).
        Invariant under preemption: folding k generated tokens into the
        recompute prefill grows the prompt by k and shrinks the
        remaining budget by k.  A sliding-window ring caps the peak at
        ceil(window / block_size) whatever the budget — unbounded
        generations reserve a constant ring."""
        tokens = plen + max_new - 1
        if self.window:
            tokens = min(tokens, self.window)
        return blocks_needed(tokens, self.block_size)

    def _prompt_blocks(self, plen: int) -> int:
        """Blocks a prompt prefill occupies: its length, ring-capped —
        a longer-than-window prompt wraps in place instead of growing."""
        if self.window:
            plen = min(plen, self.window)
        return blocks_needed(plen, self.block_size)

    def _table_width(self, num_tokens: int) -> int:
        """Pow2-bucketed table columns covering `num_tokens` positions
        (block-table slices are jit shapes: bucketing bounds compiles at
        O(log num_blocks) while per-step gather/kernel work tracks the
        live maximum instead of the whole pool; the mixed step reuses
        the same width for its chunk table — see bucketing.table_width).
        Ring-paged widths saturate at the ring, so unbounded windowed
        generations stay one compile family."""
        return table_width(num_tokens, self.block_size, self.num_blocks,
                           window=self.window)

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None) -> int:
        """Queue a token-id prompt; returns the request uid.

        Arena mode bounds a request to its slot (`plen + max_new_tokens
        <= capacity`); paged mode admits anything the pool can ever
        hold — the per-slot capacity check is lifted.

        Prompts are token-only: a VLM served through the engine runs
        text-only (no patch prefix) — multimodal admission inputs are a
        follow-up; use model.prefill directly for patched prompts."""
        prompt = np.asarray(prompt, np.int32)
        assert prompt.ndim == 1 and prompt.size > 0, prompt.shape
        assert max_new_tokens >= 1, max_new_tokens
        if self.paged:
            need = self._worst_case_blocks(len(prompt), max_new_tokens)
            if need > self.num_blocks:
                raise ValueError(
                    f"prompt ({len(prompt)}) + max_new_tokens "
                    f"({max_new_tokens}) needs {need} KV blocks; the pool "
                    f"has {self.num_blocks} (raise num_blocks)")
        elif len(prompt) + max_new_tokens > self.capacity:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f" exceeds slot capacity {self.capacity}; use "
                "Engine(paged=True) for longer-than-slot generations")
        uid = self._next_uid
        self._next_uid += 1
        self._queue.append(Request(uid, prompt, int(max_new_tokens),
                                   None if eos_id is None else int(eos_id)))
        return uid

    @property
    def pending(self) -> int:
        """Queued requests not yet admitted to a slot."""
        return len(self._queue)

    @property
    def num_active(self) -> int:
        """Requests currently decoding in the batch."""
        return sum(r is not None for r in self._slot_req)

    @property
    def free_blocks(self) -> Optional[int]:
        """Unallocated, unreserved pool blocks; None in arena mode —
        the arena has no pool, and 0 would read as "pool exhausted"."""
        return self._allocator.available if self.paged else None

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------

    def _admit(self, req: Request, slot: int):
        """Launch the prefill of `req` into `slot` (non-blocking) and
        mark the slot live.  Returns (req, slot, device token) for
        `_resolve_admission` — the first token is NOT fetched here, so
        the host can launch further admissions and the decode step
        without convoying on this prefill."""
        plen = len(req.prompt)
        if self._pad_prompts:
            sp = min(bucket_length(plen, _PREFILL_FLOOR), self.capacity)
        else:
            sp = plen
        self.prefill_shapes.add(sp)
        toks = np.zeros((1, sp), np.int32)
        toks[0, :plen] = req.prompt
        tok_dev, self._caches = self._prefill(
            self.params, toks, np.int32(plen), np.int32(slot), self._caches)
        self._slot_req[slot] = req
        self._gen[slot] = []
        self._lengths[slot] = plen
        self._lengths_dirty = True
        return req, slot, tok_dev

    def _admit_paged(self, req: Request, slot: int):
        """Chunked prefill of `req` into pool blocks tracked by the
        slot's block table (launches only — same contract as `_admit`).
        The caller already checked admissibility; this allocates the
        (re-)prefill sequence's blocks now and, under "reserve", also
        reserves the decode worst case so lazy per-step allocation can
        never fail.  A recompute re-admission runs the identical prompt
        prefill its first admission ran (same chunks, same offsets,
        same pow2 table-width bucket — no new jit shapes, host or
        mesh), then queues its generated-so-far tokens for replay
        through the shared decode step and returns None (its first
        token is already known — nothing to resolve)."""
        seq = req.prompt
        plen = len(seq)
        n_prompt = self._prompt_blocks(plen)
        blocks = self._allocator.alloc(n_prompt)
        if self.preemption == "reserve":
            need = self._worst_case_blocks(len(req.prompt),
                                           req.max_new_tokens)
            self._allocator.reserve(need - n_prompt)
            self._slot_reserved[slot] = need - n_prompt
        self._tables[slot, :n_prompt] = blocks
        self._tables_dirty = True
        # slice the table to the prompt's bucketed width: chunk-pad
        # positions past it are routed to the null block by the scatter
        table = self._tables[slot, :self._table_width(plen)].copy()

        c = self.prefill_chunk
        self.prefill_shapes.add(c)
        tok_dev = None
        for i in range(chunks_needed(plen, c)):
            chunk = seq[i * c:(i + 1) * c]
            toks = np.zeros((1, c), np.int32)
            toks[0, :len(chunk)] = chunk
            tok_dev, self._caches = self._prefill(
                self.params, toks, np.int32(len(chunk)),
                np.int32(i * c), table, self._caches)
        self._slot_req[slot] = req
        self._gen[slot] = []
        self._lengths[slot] = plen
        self._lengths_dirty = True
        if req.gen_prefix:
            # resume, don't restart: the prompt KV is rebuilt (prefill
            # token discarded — it would just re-derive gen_prefix[0])
            # and the generated tokens are queued to replay through the
            # decode step, each rewriting its KV entry with the same
            # kernel that wrote it originally.  After replay drains,
            # state is bit-for-bit the state of an uninterrupted run at
            # the eviction point.
            self._cur[slot] = req.gen_prefix[0]
            self._cur_dirty = True
            self._replay[slot] = deque(req.gen_prefix[1:])
            return None
        return req, slot, tok_dev

    def _resolve_admission(self, req: Request, slot: int,
                           tok: int) -> Optional[Request]:
        """Record a resolved first token; returns the request if it
        finished already (budget 1 or EOS on the first token)."""
        self._gen[slot] = [tok]
        self._cur[slot] = tok
        self._cur_dirty = True
        remaining = req.max_new_tokens - len(req.gen_prefix)
        if (remaining == 1
                or (req.eos_id is not None and tok == req.eos_id)):
            return self._finish(slot)
        return None

    def _finish(self, slot: int) -> Request:
        req = self._slot_req[slot]
        req.output = np.asarray(req.gen_prefix + self._gen[slot], np.int32)
        self._slot_req[slot] = None
        self._gen[slot] = []
        if self.paged:
            # free the slot's blocks + any unused worst-case reservation
            # (EOS before the budget; "recompute" never reserved); zero
            # the table/length so the dead row only ever touches the
            # null block
            self._allocator.free_partial(self._tables[slot])
            self._allocator.unreserve(self._slot_reserved[slot])
            self._slot_reserved[slot] = 0
            self._tables[slot] = 0
            self._lengths[slot] = 0
            self._tables_dirty = True
            self._lengths_dirty = True
        self._done.append(req)
        return req

    def _preempt(self, slot: int) -> None:
        """Evict the request running in `slot`: fold its generated
        tokens into a recompute prefix, free its blocks, and re-queue it
        in uid position.  Running uids are always lower than every
        never-admitted queued uid (admission is strictly FIFO), so the
        insertion point lies within the prefix of earlier evictees
        still waiting at the head — the queue stays globally uid-sorted
        and no request ever overtakes an older one."""
        req = self._slot_req[slot]
        req.gen_prefix.extend(self._gen[slot])
        req.preemptions += 1
        self.num_preemptions += 1
        self._slot_req[slot] = None
        self._gen[slot] = []
        self._replay[slot] = deque()  # rebuilt from gen_prefix on re-admission
        # a mid-stream / staged slot holds its blocks in a private table
        # (the slot's own row is still zeroed); evicting it cancels the
        # in-flight admission — the launches already dispatched write
        # into freed blocks, which the overwrite-before-valid invariant
        # makes inert (every block is fully rewritten by whatever
        # prefill re-allocates it before any position becomes valid)
        if self._stream is not None and self._stream["slot"] == slot:
            self._allocator.free_partial(self._stream["table"])
            self._stream = None
        elif any(e["slot"] == slot for e in self._staged):
            e = next(e for e in self._staged if e["slot"] == slot)
            self._staged.remove(e)
            self._allocator.free_partial(e["table"])
        else:
            self._allocator.free_partial(self._tables[slot])
        self._tables[slot] = 0
        self._lengths[slot] = 0
        self._cur[slot] = 0
        self._tables_dirty = True
        self._lengths_dirty = True
        self._cur_dirty = True
        i = 0
        while i < len(self._queue) and self._queue[i].uid < req.uid:
            i += 1
        self._queue.insert(i, req)

    def _can_admit(self, req: Request) -> bool:
        if not self.paged:
            return True
        worst = self._worst_case_blocks(len(req.prompt), req.max_new_tokens)
        if self.preemption == "reserve":
            return self._allocator.available >= worst
        # optimistic: admit against blocks free *right now* — the
        # prompt's blocks, leaving a watermark of spare blocks so the
        # first boundary crossing doesn't immediately trigger a
        # preemption.  The watermark is waived when prompt + watermark
        # would exceed the request's lifetime worst case (already
        # bounded by the pool in submit()), else a pool-filling prompt
        # with a tiny budget could never be admitted.
        need_now = self._prompt_blocks(len(req.prompt))
        if need_now + _ADMIT_WATERMARK <= worst:
            return self._allocator.can_allocate(need_now,
                                                watermark=_ADMIT_WATERMARK)
        return self._allocator.can_allocate(worst)

    @hot_loop
    def _admit_round(self, finished: List[Request]) -> bool:
        """One admission round: launch a prefill into every admissible
        free slot (back-to-back, no host sync between launches), then
        resolve the launched first tokens in one batched pass.  Returns
        True when anything was admitted — an instant finish (budget 1 /
        EOS on the prefill token) frees its slot and blocks, so the
        caller loops for another round."""
        t0 = time.perf_counter()
        pending: List[Tuple[Request, int, object]] = []
        admitted = False
        head_blocked = False
        for slot in range(self.max_batch):
            if head_blocked or not self._queue:
                break
            if self._slot_req[slot] is not None:
                continue
            if not self._can_admit(self._queue[0]):
                head_blocked = True     # FIFO: nothing may jump the head
                break
            req = self._queue.popleft()
            admit = self._admit_paged if self.paged else self._admit
            pend = admit(req, slot)
            admitted = True
            self._stats["admissions"] += 1
            if pend is not None:
                pending.append(pend)
        self._stats["admit_host_s"] += time.perf_counter() - t0
        if pending:
            # every prefill is already in flight; the first fetch waits
            # on the first prefill while the rest keep computing
            t1 = time.perf_counter()
            # repro-lint: disable=host-sync-in-hot-loop -- batched
            # first-token resolution: ONE wait per admission round after
            # every prefill is in flight (the PR 5 contract)
            toks = [int(np.asarray(tok_dev)) for _, _, tok_dev in pending]
            self._stats["prefill_wait_s"] += time.perf_counter() - t1
            for (req, slot, _), tok in zip(pending, toks):
                f = self._resolve_admission(req, slot, tok)
                if f is not None:
                    finished.append(f)
        return admitted

    @hot_loop
    def step(self) -> List[Request]:
        """Admit queued requests into free slots, then run ONE decode
        step over the batch; returns the requests finished by this step.

        Admission is FIFO: when the queue head cannot be admitted yet
        (paged mode, not enough free blocks), later requests do not jump
        it — finished requests free its blocks on subsequent steps.
        Preempted requests re-enter in uid position (ahead of every
        never-admitted request), so eviction never lets a younger
        request overtake an older one and the queue stays uid-sorted.

        With overlap enabled (`engine.overlap`), admission prefills ride
        the decode launches (mixed steps) or dispatch asynchronously
        alongside them, and first tokens resolve a step later, after the
        decode fetch has already synced past them — same outputs,
        bitwise (tests assert it), fewer and never-blocked launches."""
        if self.overlap:
            return self._step_overlapped()
        return self._step_serialized()

    @hot_loop
    def _step_serialized(self) -> List[Request]:
        """The blocking scheduler: resolve every admission's first token
        before dispatching the decode step (overlap=False, and families
        without a mixed step)."""
        finished: List[Request] = []
        while self._admit_round(finished):
            pass    # instant finishes free slots/blocks: try again

        active = [s for s in range(self.max_batch)
                  if self._slot_req[s] is not None]
        if not active:
            return finished

        t0 = time.perf_counter()
        if self.paged:
            self._topup_blocks(active)
            t0 = time.perf_counter()
            active = [s for s in active if self._slot_req[s] is not None]
            if not active:
                return finished
            # +1: the step inserts each live row's incoming token first
            w = self._table_width(max(int(self._lengths[s]) + 1
                                      for s in active))
            if self._tables_dirty or self._tables_dev_w != w:
                self._tables_dev = self._put(
                    np.ascontiguousarray(self._tables[:, :w]))
                self._tables_dev_w = w
                self._tables_dirty = False
            if self._lengths_dirty or self._lengths_dev is None:
                self._lengths_dev = self._put(self._lengths)
                self._lengths_dirty = False
            if self._cur_dirty or self._cur_dev is None:
                self._cur_dev = self._put(self._cur)
                self._cur_dirty = False
            toks_dev, self._caches, self._lengths_dev = self._decode(
                self.params, self._cur_dev, self._caches,
                self._tables_dev, self._lengths_dev)
        else:
            if self._lengths_dirty or self._lengths_dev is None:
                self._lengths_dev = self._put(self._lengths)
                self._lengths_dirty = False
            if self._cur_dirty or self._cur_dev is None:
                self._cur_dev = self._put(self._cur)
                self._cur_dirty = False
            toks_dev, self._caches, self._lengths_dev = self._decode(
                self.params, self._cur_dev, self._caches, self._lengths_dev)
        # the decode step's outputs ARE the next step's inputs: tokens
        # and advanced lengths stay on device, and the only device→host
        # traffic is this [B] int32 fetch (greedy ids — the full-vocab
        # logits never leave the device, which on a mesh would be a
        # model-sharded cross-host gather)
        self._cur_dev = toks_dev
        t1 = time.perf_counter()
        self._stats["decode_dispatch_s"] += t1 - t0
        # repro-lint: disable=host-sync-in-hot-loop -- this [B] int32 token
        # fetch IS the per-step device->host contract (never logits)
        nxt = np.asarray(toks_dev)
        t2 = time.perf_counter()
        self._stats["decode_steps"] += 1
        self._stats["decode_fetch_s"] += t2 - t1
        self._stats["decode_s"] += t2 - t0
        self._stats["decode_fetch_elems"] = int(nxt.size)
        self._stats["decode_fetch_dtype"] = str(nxt.dtype)
        for s in active:
            self._lengths[s] += 1
            if self._replay[s]:
                # recompute replay: the step re-inserted one evicted
                # token's KV; its argmax is the already-known next
                # token, so feed that from the replay queue and skip
                # emission/EOS/budget (all checked pre-eviction)
                self._cur[s] = self._replay[s].popleft()
                self._cur_dirty = True
                self._stats["replayed_tokens"] += 1
                continue
            tok = int(nxt[s])
            self._gen[s].append(tok)
            self._cur[s] = tok
            req = self._slot_req[s]
            if (len(req.gen_prefix) + len(self._gen[s]) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id)):
                finished.append(self._finish(s))
        return finished

    def _topup_blocks(self, active: List[int]) -> None:
        """Top up the block covering this step's write position for each
        decoding row (billed to topup_host_s, not decode_s — under
        pressure this loop runs the preemption machinery, which is host
        bookkeeping, not decode-step time).

        "reserve" draws on the admission earmark (cannot fail);
        "recompute" allocates oldest-first from the free list and, when
        the pool runs dry, preempts the newest admission (LIFO) until a
        block frees up — evicting a slot always returns >= 1 block, so
        the inner loop terminates, and the oldest running request is
        never the victim while a younger one holds blocks, so it
        monotonically progresses (no livelock: every request eventually
        becomes oldest).  Mid-stream and staged admissions hold their
        slots too, and being the newest admissions they are the first
        LIFO victims — `_preempt` cancels the in-flight admission and
        frees its private table."""
        t0 = time.perf_counter()
        for s in sorted(active, key=lambda t: self._slot_req[t].uid):
            if self._slot_req[s] is None:
                continue        # preempted by an earlier top-up
            pos = int(self._lengths[s])
            if self.window:
                # ring-paged: the write lands at ring slot pos % window,
                # so once the ring's blocks exist the `!= 0` check below
                # short-circuits every subsequent step — a full-ring
                # generation allocates ZERO further blocks, however long
                pos %= self.window
            bi = pos // self.block_size
            if self._tables[s, bi] != 0:
                continue
            if self.preemption == "reserve":
                (blk,) = self._allocator.alloc(1, reserved=True)
                self._slot_reserved[s] -= 1
            else:
                while not self._allocator.can_allocate(1):
                    victim = max(
                        (t for t in range(self.max_batch)
                         if self._slot_req[t] is not None),
                        key=lambda t: self._slot_req[t].uid)
                    self._preempt(victim)
                    if victim == s:
                        break
                if self._slot_req[s] is None:
                    continue    # s itself was the newest admission
                (blk,) = self._allocator.alloc(1)
            self._tables[s, bi] = blk
            self._tables_dirty = True
        self._stats["topup_host_s"] += time.perf_counter() - t0

    # ------------------------------------------------------------------
    # overlapped admission (the async scheduler + unified mixed step)
    # ------------------------------------------------------------------

    def _start_stream(self, req: Request, slot: int) -> None:
        """Begin streaming `req`'s prefill through the decode launches.
        The slot is claimed (it counts as active and can be preempted)
        but stays DEAD to decode — zero validity length, zeroed table
        row — until `_resolve_staged` installs it; on the paged backend
        the prompt's blocks live in a private table until then, so the
        fused decode's writes for this slot route to the null block."""
        plen = len(req.prompt)
        self._slot_req[slot] = req
        self._gen[slot] = []
        if self.paged:
            n_prompt = self._prompt_blocks(plen)
            blocks = self._allocator.alloc(n_prompt)
            if self.preemption == "reserve":
                need = self._worst_case_blocks(plen, req.max_new_tokens)
                self._allocator.reserve(need - n_prompt)
                self._slot_reserved[slot] = need - n_prompt
            table = np.zeros(self.num_blocks, np.int32)
            table[:n_prompt] = blocks
            c = self.prefill_chunk
            self.prefill_shapes.add(c)
            self._stream = {"req": req, "slot": slot, "plen": plen,
                            "table": table, "n_prompt": n_prompt,
                            "i": 0, "total": chunks_needed(plen, c),
                            "tok": None}
        else:
            # overlap requires caps.pad_prompts, so the arena prompt is
            # always the bucketed padded shape the mixed step compiled
            sp = min(bucket_length(plen, _PREFILL_FLOOR), self.capacity)
            self.prefill_shapes.add(sp)
            toks = np.zeros((1, sp), np.int32)
            toks[0, :plen] = req.prompt
            self._stream = {"req": req, "slot": slot, "plen": plen,
                            "tokens": toks, "i": 0, "total": 1,
                            "tok": None}

    def _stage_admit(self, req: Request, slot: int) -> None:
        """Admit `req` with async-dispatched prefill launches: every
        launch goes in flight now, nothing is resolved, and the slot
        stays dead to decode until `_resolve_staged` (with the stream's
        landing, preserving FIFO start order).  This is the overlap
        analogue of `_admit_paged` for requests behind the stream —
        same launches, same shapes, deferred resolution.  Paged-only:
        see `_admission_phase` for why the arena cannot stage."""
        assert self.paged
        plen = len(req.prompt)
        self._slot_req[slot] = req
        self._gen[slot] = []
        n_prompt = self._prompt_blocks(plen)
        blocks = self._allocator.alloc(n_prompt)
        if self.preemption == "reserve":
            need = self._worst_case_blocks(plen, req.max_new_tokens)
            self._allocator.reserve(need - n_prompt)
            self._slot_reserved[slot] = need - n_prompt
        table = np.zeros(self.num_blocks, np.int32)
        table[:n_prompt] = blocks
        c = self.prefill_chunk
        self.prefill_shapes.add(c)
        seq = req.prompt
        tok = None
        ctab = np.ascontiguousarray(table[:self._table_width(plen)])
        for i in range(chunks_needed(plen, c)):
            chunk = seq[i * c:(i + 1) * c]
            toks = np.zeros((1, c), np.int32)
            toks[0, :len(chunk)] = chunk
            tok, self._caches = self._prefill(
                self.params, toks, np.int32(len(chunk)), np.int32(i * c),
                ctab, self._caches)
        self._staged.append({"req": req, "slot": slot, "plen": plen,
                             "tok": tok, "table": table,
                             "n_prompt": n_prompt})

    def _admission_phase(self) -> None:
        """Overlapped admission: pop the queue head into the chunk
        stream (its prefill rides the decode launches) and, on the
        paged backend, stage any further admissible requests into free
        slots with async prefill launches.  FIFO is preserved twice
        over — requests are popped strictly head-first (a blocked head
        blocks everything behind it), and staged slots only come alive
        together with the stream they queued behind.

        Bulk staging is paged-only: a staged paged prefill writes into
        private blocks while the dead slot's zeroed table row routes
        the decode launch's writes to the null block, but the arena
        decode ring-inserts at a cache-carried per-slot ptr — a decode
        launch after a staged arena prefill would advance that ptr and
        clobber position plen of the freshly written row.  The arena
        admits through the stream only, where the mixed trace runs the
        prefill AFTER the decode and `_write_slot` overwrites the whole
        row (garbage included) and resets the ptr."""
        t0 = time.perf_counter()
        free = deque(s for s in range(self.max_batch)
                     if self._slot_req[s] is None)
        # async-mode paged admission needs no stream at all: chunk
        # launches are write-disjoint from the decode whatever their
        # dispatch order, so the queue head bulk-stages like everyone
        # behind it — all its chunks go in flight this step instead of
        # riding one decode launch each (the one-chunk-per-step stream
        # exists for the fused trace, which carries exactly one chunk).
        # Skipping the stream also keeps the decode table width at the
        # active rows' own bucket: no per-stream widen/shrink churn.
        stream_ok = not (self.paged and self._mixed is None)
        if (stream_ok and self._stream is None and self._queue and free
                and self._can_admit(self._queue[0])):
            self._start_stream(self._queue.popleft(), free.popleft())
            self._stats["admissions"] += 1
        while (self.paged and self._queue and free
               and self._can_admit(self._queue[0])):
            self._stage_admit(self._queue.popleft(), free.popleft())
            self._stats["admissions"] += 1
        self._stats["admit_host_s"] += time.perf_counter() - t0

    def _drain_stream(self) -> None:
        """Flush an in-flight stream's remaining prefill launches
        through the plain prefill step and stage it for resolution —
        the no-decode-rows path (nothing to ride; equivalent to the
        serialized admission, which is exactly what the situation is)."""
        st, self._stream = self._stream, None
        t0 = time.perf_counter()
        if not self.paged:
            tok, self._caches = self._prefill(
                self.params, st["tokens"], np.int32(st["plen"]),
                np.int32(st["slot"]), self._caches)
            entry = {"req": st["req"], "slot": st["slot"],
                     "plen": st["plen"], "tok": tok}
        else:
            seq = st["req"].prompt
            c = self.prefill_chunk
            ctab = np.ascontiguousarray(
                st["table"][:self._table_width(st["plen"])])
            tok = st["tok"]
            for i in range(st["i"], st["total"]):
                chunk = seq[i * c:(i + 1) * c]
                toks = np.zeros((1, c), np.int32)
                toks[0, :len(chunk)] = chunk
                tok, self._caches = self._prefill(
                    self.params, toks, np.int32(len(chunk)),
                    np.int32(i * c), ctab, self._caches)
            entry = {"req": st["req"], "slot": st["slot"],
                     "plen": st["plen"], "tok": tok, "table": st["table"],
                     "n_prompt": st["n_prompt"]}
        self._stats["admit_host_s"] += time.perf_counter() - t0
        self._staged.append(entry)

    @hot_loop
    def _resolve_staged(self, finished: List[Request],
                        deferred: bool = True) -> None:
        """Install every staged admission whose prefill generation has
        landed: block table + validity length first (the slot becomes
        decode-visible), then the first token — or the replay queue for
        a recompute re-admission, whose first token is already known.

        Held back while a stream is in flight: the stream is always the
        OLDEST unresolved admission (heads pop strictly in order), so
        resolving younger staged slots early would let them start
        decoding ahead of it and break FIFO completion order.  Resolved
        oldest-first for the same reason.

        In the deferred case (step start) the token fetch costs ~zero
        wall time: the previous step ended by fetching the [B] decode
        tokens of the very launch generation that produced these
        prefill tokens, so the device has already caught up."""
        if self._stream is not None or not self._staged:
            return
        t1 = time.perf_counter()
        entries = sorted(self._staged, key=lambda e: e["req"].uid)
        self._staged = []
        for e in entries:
            req, slot, plen = e["req"], e["slot"], e["plen"]
            if self.paged:
                n = e["n_prompt"]
                self._tables[slot, :n] = e["table"][:n]
                self._tables_dirty = True
            self._lengths[slot] = plen
            self._lengths_dirty = True
            if deferred:
                self._stats["overlapped_admissions"] += 1
            if req.gen_prefix:
                # recompute re-admission: resume from the replay queue
                # (the prefill's token would just re-derive gen_prefix[0])
                self._cur[slot] = req.gen_prefix[0]
                self._cur_dirty = True
                self._replay[slot] = deque(req.gen_prefix[1:])
                continue
            # repro-lint: disable=host-sync-in-hot-loop -- deferred
            # first-token resolution: the prior step's [B] decode fetch
            # already synced past the launch that produced this token
            tok = int(np.asarray(e["tok"]))
            f = self._resolve_admission(req, slot, tok)
            if f is not None:
                finished.append(f)
        self._stats["prefill_wait_s"] += time.perf_counter() - t1

    @hot_loop
    def _step_overlapped(self) -> List[Request]:
        """One scheduler pass of the overlapped engine: install staged
        admissions, launch this step's admissions asynchronously, then
        dispatch ONE decode launch — mixed with the stream's prefill
        unit when a stream is in flight — without ever blocking on a
        first token between admission and dispatch."""
        finished: List[Request] = []
        self._resolve_staged(finished)
        self._admission_phase()

        st_slot = self._stream["slot"] if self._stream is not None else -1
        staged_slots = {e["slot"] for e in self._staged}
        active = [s for s in range(self.max_batch)
                  if self._slot_req[s] is not None
                  and s != st_slot and s not in staged_slots]
        if not active:
            # no decode launch to overlap with: flush + resolve now
            # (cold start / everything just finished — the serialized
            # admission cost is genuinely unavoidable here)
            if self._stream is not None:
                self._drain_stream()
            self._resolve_staged(finished, deferred=False)
            active = [s for s in range(self.max_batch)
                      if self._slot_req[s] is not None]
            if not active:
                return finished

        if self.paged:
            self._topup_blocks(active)
            t0 = time.perf_counter()
            active = [s for s in active if self._slot_req[s] is not None]
            if not active:
                return finished
            # one width covers the decode tables AND the stream's chunk
            # table, so a mixed launch adds no new width families
            hi = max(int(self._lengths[s]) + 1 for s in active)
            if self._stream is not None:
                hi = max(hi, self._stream["plen"])
            w = self._table_width(hi)
            if self._tables_dirty or self._tables_dev_w != w:
                self._tables_dev = self._put(
                    np.ascontiguousarray(self._tables[:, :w]))
                self._tables_dev_w = w
                self._tables_dirty = False
            if self._lengths_dirty or self._lengths_dev is None:
                self._lengths_dev = self._put(self._lengths)
                self._lengths_dirty = False
            if self._cur_dirty or self._cur_dev is None:
                self._cur_dev = self._put(self._cur)
                self._cur_dirty = False
            if self._stream is not None:
                st = self._stream
                c = self.prefill_chunk
                chunk = st["req"].prompt[st["i"] * c:(st["i"] + 1) * c]
                ctoks = np.zeros((1, c), np.int32)
                ctoks[0, :len(chunk)] = chunk
                if self._mixed is not None:
                    toks_dev, self._caches, self._lengths_dev, p_tok = \
                        self._mixed(self.params, self._cur_dev, self._caches,
                                    self._tables_dev, self._lengths_dev,
                                    ctoks, np.int32(len(chunk)),
                                    np.int32(st["i"] * c),
                                    np.ascontiguousarray(st["table"][:w]))
                    self._stats["mixed_steps"] += 1
                else:
                    # async composition: the same decode and chunk-prefill
                    # graphs the serialized scheduler runs, dispatched
                    # back-to-back with no fetch in between (write sets
                    # disjoint: the dead slot routes to the null block,
                    # the chunk writes its private blocks)
                    toks_dev, self._caches, self._lengths_dev = self._decode(
                        self.params, self._cur_dev, self._caches,
                        self._tables_dev, self._lengths_dev)
                    p_tok, self._caches = self._prefill(
                        self.params, ctoks, np.int32(len(chunk)),
                        np.int32(st["i"] * c),
                        np.ascontiguousarray(
                            st["table"][:self._table_width(st["plen"])]),
                        self._caches)
                st["i"] += 1
                st["tok"] = p_tok
                if st["i"] == st["total"]:
                    self._stream = None
                    self._staged.append(
                        {"req": st["req"], "slot": st["slot"],
                         "plen": st["plen"], "tok": p_tok,
                         "table": st["table"], "n_prompt": st["n_prompt"]})
            else:
                toks_dev, self._caches, self._lengths_dev = self._decode(
                    self.params, self._cur_dev, self._caches,
                    self._tables_dev, self._lengths_dev)
        else:
            t0 = time.perf_counter()
            if self._lengths_dirty or self._lengths_dev is None:
                self._lengths_dev = self._put(self._lengths)
                self._lengths_dirty = False
            if self._cur_dirty or self._cur_dev is None:
                self._cur_dev = self._put(self._cur)
                self._cur_dirty = False
            if self._stream is not None:
                st = self._stream
                if self._mixed is not None:
                    toks_dev, self._caches, self._lengths_dev, p_tok = \
                        self._mixed(self.params, self._cur_dev, self._caches,
                                    self._lengths_dev, st["tokens"],
                                    np.int32(st["plen"]),
                                    np.int32(st["slot"]))
                    self._stats["mixed_steps"] += 1
                else:
                    # async composition: decode FIRST (the dead slot's
                    # garbage ring write must land before the prefill
                    # overwrites the whole row and resets its ptr — the
                    # same order the mixed trace uses), then the same
                    # slot-prefill graph the serialized scheduler runs,
                    # with no fetch in between
                    toks_dev, self._caches, self._lengths_dev = self._decode(
                        self.params, self._cur_dev, self._caches,
                        self._lengths_dev)
                    p_tok, self._caches = self._prefill(
                        self.params, st["tokens"], np.int32(st["plen"]),
                        np.int32(st["slot"]), self._caches)
                self._stream = None
                self._staged.append({"req": st["req"], "slot": st["slot"],
                                     "plen": st["plen"], "tok": p_tok})
            else:
                toks_dev, self._caches, self._lengths_dev = self._decode(
                    self.params, self._cur_dev, self._caches,
                    self._lengths_dev)
        self._cur_dev = toks_dev
        t1 = time.perf_counter()
        self._stats["decode_dispatch_s"] += t1 - t0
        # repro-lint: disable=host-sync-in-hot-loop -- this [B] int32 token
        # fetch IS the per-step device->host contract (never logits)
        nxt = np.asarray(toks_dev)
        t2 = time.perf_counter()
        self._stats["decode_steps"] += 1
        self._stats["decode_fetch_s"] += t2 - t1
        self._stats["decode_s"] += t2 - t0
        self._stats["decode_fetch_elems"] = int(nxt.size)
        self._stats["decode_fetch_dtype"] = str(nxt.dtype)
        # uid order, not slot order: overlapped slot assignment does not
        # track uid order across stream generations, and same-step
        # finishes must still complete oldest-first
        for s in sorted(active, key=lambda t: self._slot_req[t].uid):
            self._lengths[s] += 1
            if self._replay[s]:
                self._cur[s] = self._replay[s].popleft()
                self._cur_dirty = True
                self._stats["replayed_tokens"] += 1
                continue
            tok = int(nxt[s])
            self._gen[s].append(tok)
            self._cur[s] = tok
            req = self._slot_req[s]
            if (len(req.gen_prefix) + len(self._gen[s]) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id)):
                finished.append(self._finish(s))
        return finished

    def run(self) -> List[Request]:
        """Drain queue + batch; returns every request completed so far
        (accumulating across earlier step() calls).  Mid-stream and
        staged admissions hold their slots (they count as active), so
        the loop cannot exit with an admission half-landed."""
        while self._queue or self.num_active:
            self.step()
        return list(self._done)
