"""Continuous-batching serving engine with fused multi-token decode.

Slot-based: the engine owns a KV cache with ``n_slots`` sequences. Queued
requests are admitted with **batched bucket admission**: all waiting
prompts that fall in the same padded-length bucket (up to the number of
free slots) are prefilled in ONE compiled call — per-row ``logits_at``
indices make ragged real lengths inside a bucket exact — then all active
slots decode in lockstep HLO with per-slot positions (the cache/ring masks
make ragged depths correct — see models/attention.py). Finished slots are
refilled from the queue mid-decode: continuous batching.

With ``EngineConfig(cache="paged")`` the dense rows are replaced by the
block/paged KV cache (models/cache.py + serving/cache.py): admission
reserves ``ceil(tokens / block_size)`` physical pages per request out of
a shared pool, so in-flight concurrency is bounded by the BLOCK budget,
not ``n_slots``, and ragged prompts pay no cache padding (prefill still
pads its compute batch to ``PROMPT_BUCKETS`` to bound compiled shapes).
Admission is strict FIFO with no bucket barrier: consecutive queue heads
sharing an admit key batch into one prefill, and a head that doesn't fit
stalls admission rather than being scanned past. Greedy decode through
the paged path is bit-identical to the dense baseline — masked (scratch
/ garbage) positions contribute an exact 0.0 to the attention
accumulator, the parity the paged tests pin down.

Decode runs in **macro-steps**: each ``step()`` admits, then runs one
fused chunk of up to ``chunk_tokens`` decode iterations entirely on
device (``Model.decode_chunk`` — a ``lax.scan`` with sampling and stop
conditions in-graph), paying one XLA dispatch and one host transfer per
chunk instead of per token. The chunk jit **donates the KV cache** (as
does the admission row-scatter), so decode writes the paged pool in
place (``Model.decode_chunk`` says how) — after a step the previous
cache buffers are invalid, which is why the
engine always replaces ``self.cache`` with the returned tree. Chunk
length defaults to the roofline cost model
(``core/roofline.decode_chunk_tokens``) and is clamped each step by the
shortest ``remaining`` among active slots (and their ``max_len``
headroom) so no decode iteration is wasted on a finished slot.
``chunked=False`` keeps the one-dispatch-per-token path as a measurable
baseline (see benchmarks/decode_throughput.py).

The engine is step-driven and non-blocking at the scheduling level:
``step()`` performs at most one admission round plus one decode chunk and
returns whether work remains, so a pool can interleave many engines (one
per container) from worker threads — jax releases the GIL during device
dispatch, which is what makes the concurrent container pool in
serving/pool.py actually overlap. ``busy_s`` accumulates the wall time the
engine spent inside ``step()`` and feeds the pool's energy proxy;
``tokens_generated`` counts emitted tokens at the same per-chunk
granularity, so pools can surface per-container tokens/s.

Engines sharing one ``Model`` share jitted prefill/decode executables
(module-level cache) so an n-container pool compiles each shape once, not
n times (jit re-specialises per device placement under that cache, so
engines on different sub-meshes stay correct).

An engine can be **pinned to a sub-mesh**: pass ``mesh`` (one of the
disjoint per-container meshes from ``launch/mesh.make_container_meshes``)
and the engine instantiates ``ShardingRules`` on it and commits its params
and KV cache onto that device slice with ONE ``jax.device_put`` replication
at construction — reused across every wave the pool serves. All jitted
calls then execute on the sub-mesh (committed inputs pin the computation),
cache donation included, and outputs never leave the slice; replicated
placement keeps the container bit-identical to the single-device baseline
(see launch/sharding.ShardingRules.container_placement). On a sub-mesh of
several devices the model programs run once per device under
``shard_map`` (each device holds a whole replica): the TPU compiler does
not partition a Pallas kernel by itself.

This is the per-container serving loop; core/splitter.py +
serving/pool.py run n of these over disjoint resource shares — the paper's
method end-to-end.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
import warnings
import weakref
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec

from repro.core.roofline import decode_chunk_tokens
from repro.models.cache import PagedLayout
from repro.models.model import Model
from repro.serving.cache import DenseCache, PagedCache
from repro.serving.events import ChunkEvent, DoneEvent, FailedEvent


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int
    extras: dict = dataclasses.field(default_factory=dict)
    # seconds the request may spend in the serving stack before it is
    # cancelled (None = no deadline). The Router stamps its own clock at
    # submit; the engine re-stamps on arrival, so engine-side expiry is
    # a resource-freeing approximation and the Router's check is the
    # authoritative end-to-end one.
    deadline_s: float | None = None
    # SLO class name and tenant id (serving/router.py + workload/slo.py):
    # the Router's priority-ordered dispatch, per-class shed thresholds
    # and per-tenant quotas key on these; the engine itself ignores both.
    priority: str = "default"
    tenant: str = ""


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list
    prompt_len: int
    latency_s: float = 0.0
    # prompt positions satisfied by prefix-cache hits (0 without
    # prefix_cache): the Router aggregates these into WindowStats so the
    # scheduler observes the EFFECTIVE post-hit prefill load
    prefix_hit_tokens: int = 0


# THE prompt-length bucket table. The engine's padded batch admission and
# the router's bucket-aware tie-breaking must agree on it, so it lives
# here once — a paged engine admits at real lengths (no buckets in the
# cache), but its prefill COMPUTE still pads to these buckets to bound
# the number of compiled prefill shapes.
PROMPT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)


def _bucket(n: int, buckets=PROMPT_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    # past the table: round up to the next power of two, so ragged long
    # prompts share prefill executables instead of each distinct length
    # compiling its own (a compile spike mid-serving)
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Frozen, picklable configuration for one ServingEngine.

    ``cache="dense"`` is the bit-parity baseline: ``n_slots`` private
    ``(max_len, ...)`` cache rows. ``cache="paged"`` switches every
    pageable layer group to the block cache (models/cache.py): a pool of
    ``max_blocks`` shared pages of ``block_size`` tokens, per-sequence
    block tables, and up to ``max_seqs`` resident sequences — in-flight
    concurrency is then bounded by the block budget, not ``n_slots``.

    Defaults keep ``max_blocks`` at the dense footprint
    (``n_slots × max_len / block_size``): same HBM, strictly more
    admissible short requests.
    """
    n_slots: int = 4
    max_len: int = 512
    cache: str = "dense"
    block_size: int = 16
    max_blocks: int | None = None
    max_seqs: int | None = None
    # prefix sharing (paged only): index full prompt blocks by content
    # hash, map new requests' leading blocks onto cache hits (copy-on-
    # write), and prefill only the residual suffix. Architectures the
    # suffix path can't serve bit-exactly (SSM/hybrid state, sliding
    # windows, MLA latents, int8 pages, non-rope positions) silently
    # degrade to no sharing — outputs stay identical either way.
    prefix_cache: bool = False
    dtype: Any = jnp.float32
    greedy: bool = True
    seed: int = 0
    batch_admit: bool = True
    chunked: bool = True
    chunk_tokens: int | None = None

    def __post_init__(self):
        if self.cache not in ("dense", "paged"):
            raise ValueError(f"cache must be 'dense' or 'paged', "
                             f"got {self.cache!r}")
        if self.cache == "paged" and self.max_len % self.block_size:
            raise ValueError(
                f"max_len={self.max_len} must be a multiple of "
                f"block_size={self.block_size} (a sequence's logical "
                "blocks must tile the horizon exactly)")
        if self.prefix_cache and self.cache != "paged":
            raise ValueError("prefix_cache requires cache='paged' (hits "
                             "are shared physical pages)")

    @property
    def resolved_max_blocks(self) -> int:
        if self.max_blocks is not None:
            return self.max_blocks
        return max(1, self.n_slots * self.max_len // self.block_size)

    @property
    def resolved_max_seqs(self) -> int:
        return (self.max_seqs if self.max_seqs is not None
                else self.resolved_max_blocks)

    @property
    def n_rows(self) -> int:
        """Resident-sequence capacity = batch dim of the engine cache."""
        return (self.resolved_max_seqs if self.cache == "paged"
                else self.n_slots)


@dataclasses.dataclass
class _Slot:
    active: bool = False
    rid: int = -1
    pos: int = 0                  # next position to write
    prompt_len: int = 0           # true prompt length, recorded at admission
    remaining: int = 0
    generated: list = dataclasses.field(default_factory=list)
    started: float = 0.0          # perf_counter stamp (monotonic)
    deadline: float | None = None  # absolute perf_counter expiry stamp
    hit_tokens: int = 0           # prefix-cache hit positions (sharing)


# jitted executables shared by every engine built on the same Model —
# populated lazily, keyed by (kind, *static shape info)
_JIT_CACHE: "weakref.WeakKeyDictionary[Model, dict]" = \
    weakref.WeakKeyDictionary()


def _shared_jits(model: Model) -> dict:
    cache = _JIT_CACHE.get(model)
    if cache is None:
        cache = _JIT_CACHE.setdefault(model, {})
    return cache


class ServingEngine:
    # streaming hook: backends set ``on_event`` to receive a ChunkEvent
    # per request per macro-step (built from the chunk's existing host
    # transfer — streaming adds no device syncs) and a DoneEvent per
    # completion; ``container_id`` stamps the emitting container into
    # every event. ``fault`` is the test-only FaultInjector hook
    # (serving/faults.py) consulted at the top of every step and at each
    # paged block allocation. Class-level defaults keep every existing
    # engine_factory signature working unchanged.
    on_event: Callable[[Any], None] | None = None
    container_id: int = 0
    fault: Any = None
    # rids popped off the queue by the admission round in progress and
    # not yet in a slot: a step that raises there (a prefill that fails
    # to compile) must still report them lost
    admitting: tuple = ()

    def __init__(self, model: Model, params: Any,
                 config: EngineConfig | None = None, *,
                 mesh=None, rules=None, **legacy_kw):
        if legacy_kw:
            if config is not None:
                raise TypeError(
                    "pass either an EngineConfig or legacy keyword "
                    f"arguments, not both (got {sorted(legacy_kw)})")
            warnings.warn(
                "ServingEngine(model, params, n_slots=..., ...) keyword "
                "arguments are deprecated; pass "
                "ServingEngine(model, params, EngineConfig(...)) instead",
                DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy_kw)
        if config is None:
            config = EngineConfig()
        self.config = config
        self.model = model
        self.params = params
        self.n_slots = config.n_slots
        self.max_len = config.max_len
        self.paged = config.cache == "paged"
        self.layout = (PagedLayout(config.block_size,
                                   config.resolved_max_blocks)
                       if self.paged else None)
        n_rows = config.n_rows
        dtype, layout = config.dtype, self.layout
        self.mesh = mesh
        self.rules = rules
        if mesh is not None and rules is None:
            from repro.launch.sharding import ShardingRules
            self.rules = ShardingRules(mesh, train=False, fsdp=False)
        if self.rules is not None:
            # the one per-container placement: params committed onto this
            # container's device slice (reused across waves), and the KV
            # cache allocated directly ON the slice (out_shardings) rather
            # than materialised on the default device and copied over —
            # pool construction must not route n caches through device 0
            self.params = jax.device_put(
                params, self.rules.container_placement(params))
            def init_cache():
                return model.init_cache(n_rows, config.max_len, dtype,
                                        layout=layout)
            placement = self.rules.container_placement(
                jax.eval_shape(init_cache))
            tree = jax.jit(init_cache, out_shardings=placement)()
        else:
            tree = model.init_cache(n_rows, config.max_len, dtype,
                                    layout=layout)
        self.device_set = (self.rules.device_set if self.rules is not None
                           else frozenset())
        # a sub-mesh of several devices holds one replica per device; its
        # programs are per-device (see _jit) and keyed by the mesh
        self._replicas = (mesh if mesh is not None and mesh.devices.size > 1
                          else None)
        self.slots = [_Slot() for _ in range(n_rows)]
        self.queue: deque[Request] = deque()
        self.done: list[Completion] = []
        self.greedy = config.greedy
        self.batch_admit = config.batch_admit
        self.chunked = config.chunked
        self.chunk_tokens = (
            config.chunk_tokens if config.chunk_tokens is not None
            else decode_chunk_tokens(
                model.cfg, n_rows,
                context_tokens=config.max_len if self.paged else 0))
        self._key = jax.random.PRNGKey(config.seed)
        self._jits = _shared_jits(model)
        key = ("decode", self._replicas)
        if key not in self._jits:
            self._jits[key] = self._jit(model.decode_step)
        self._decode = self._jits[key]
        # which axis of each cache leaf is the batch/slot axis (None for
        # scalar or batch-free leaves) — inferred once from shape structs so
        # row insertion never has to guess from runtime shapes (which is
        # ambiguous when a prefill batch happens to equal n_slots). Always
        # derived from the DENSE layout: it describes the prefill
        # mini-cache rows both backends scatter from.
        ml = config.max_len
        one = jax.eval_shape(lambda: model.init_cache(1, ml, dtype))
        two = jax.eval_shape(lambda: model.init_cache(2, ml, dtype))
        self._batch_axes = jax.tree.map(
            lambda a, b: next((i for i, (x, y) in
                               enumerate(zip(a.shape, b.shape)) if x != y),
                              None), one, two)
        # prefix-sharing eligibility: the suffix-prefill path is bit-exact
        # only for full-horizon rope GQA over all-paged groups — SSM /
        # hybrid state, sliding windows (gemma locals, mixtral), MLA
        # latents, int8 pages and learned positions (whisper) fall back
        # to the plain paged path (hit_tokens stays 0, outputs identical)
        cfg = model.cfg
        self._share = (self.paged and config.prefix_cache
                       and model.fam in ("dense", "moe")
                       and not cfg.mla
                       and cfg.sliding_window == 0
                       and cfg.kv_cache_dtype != "int8"
                       and cfg.pos_embed == "rope")
        if self.paged:
            self.cache_backend = PagedCache(tree, n_rows, layout, ml,
                                            self._batch_axes, self._jits,
                                            prefix_cache=self._share)
        else:
            self.cache_backend = DenseCache(tree, n_rows,
                                            self._batch_axes, self._jits)
        self._deadline_abs: dict[int, float] = {}  # rid -> expiry (queued)
        self.steps = 0                # step() calls that found work
        self.chunks = 0               # fused decode chunks dispatched
        self.tokens_generated = 0     # tokens emitted (prefill + decode)
        self.prefill_tokens_executed = 0  # real positions run in prefill
        self.prefix_hit_tokens_total = 0  # positions served from hits
        self.busy_s = 0.0             # wall time spent inside step()
        self.peak_active = 0          # max concurrently active rows seen
        # MoE families: decoded tokens' assignments to the experts this
        # chip holds, and those of them not computed (0: moe_serve is
        # dropless), summed over layers and chunks
        self.moe_held_assignments = 0
        self.moe_dropped_assignments = 0
        self.budget_exhausted = False  # last run() hit max_steps with work

    @property
    def cache(self) -> Any:
        """The device cache tree (owned by the cache backend)."""
        return self.cache_backend.tree

    @cache.setter
    def cache(self, tree: Any) -> None:
        self.cache_backend.tree = tree

    # ------------------------------------------------------------------
    def _emit_chunk(self, rid: int, tokens, now: float) -> None:
        if self.on_event is not None:
            self.on_event(ChunkEvent(rid, self.container_id,
                                     tuple(tokens), now))

    def _emit_done(self, comp: Completion, now: float) -> None:
        if self.on_event is not None:
            self.on_event(DoneEvent(comp.rid, self.container_id, comp, now))

    def _emit_fail(self, rid: int, kind: str, reason: str,
                   now: float) -> None:
        if self.on_event is not None:
            self.on_event(FailedEvent(rid, self.container_id, kind,
                                      reason, now))

    def submit(self, req: Request) -> None:
        if req.max_new_tokens <= 0:
            # zero-budget requests complete empty without touching the
            # device: seeding a slot would emit the prefill sample, one
            # token the request never asked for. Handled at submission so
            # the admission fast path never rescans the queue for them.
            comp = Completion(req.rid, [], len(req.prompt))
            self.done.append(comp)
            self._emit_done(comp, time.perf_counter())
            return
        if req.deadline_s is not None:
            self._deadline_abs[req.rid] = (time.perf_counter()
                                           + req.deadline_s)
        self.queue.append(req)

    def submit_many(self, reqs) -> None:
        for r in reqs:
            self.submit(r)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s.active for s in self.slots)

    @property
    def _pad_ok(self) -> bool:
        """Right-padding a prompt is harmless only for non-recurrent,
        non-windowed caches (pad K/V slots stay masked until overwritten;
        SSM states and ring windows would absorb the garbage)."""
        cfg = self.model.cfg
        return not (cfg.is_ssm or cfg.sliding_window > 0)

    def _jit(self, fn, **jit_kw):
        """``jax.jit`` for this engine's placement: on a multi-device
        sub-mesh every device runs ``fn`` whole on its own replica
        (``shard_map`` with everything replicated). The body has no
        collectives, and a Pallas call's output carries no varying-axes
        annotation, so the varying-axes check is off."""
        if self._replicas is not None:
            rep = PartitionSpec()
            fn = jax.shard_map(fn, mesh=self._replicas, in_specs=rep,
                               out_specs=rep, check_vma=False)
        return jax.jit(fn, **jit_kw)

    def _prefill_fn(self, n_seqs: int, bl: int):
        key = ("prefill", n_seqs, bl, self.max_len, self._replicas)
        if key not in self._jits:
            m, ml = self.model, self.max_len

            def prefill(params, batch, logits_idx):
                cache = m.init_cache(n_seqs, ml)
                return m.prefill(params, batch, cache, logits_at=logits_idx)
            self._jits[key] = self._jit(prefill)
        return self._jits[key]

    def _suffix_prefill_fn(self, n_seqs: int, bl: int, offset: int):
        """Residual-suffix prefill executable: ``offset`` is static (it
        fixes the rope positions and the context width), ``bl`` is the
        PROMPT_BUCKETS-padded suffix width — suffix shapes reuse the same
        bucket table as full prefill, so compiled-shape count stays
        bounded."""
        key = ("prefill_sfx", n_seqs, bl, offset, self.max_len,
               self._replicas)
        if key not in self._jits:
            m = self.model

            def suffix_prefill(params, batch, ctx, logits_idx):
                cache = m.init_cache(n_seqs, bl)
                return m.prefill_suffix(params, batch, cache, ctx, offset,
                                        logits_at=logits_idx)
            self._jits[key] = self._jit(suffix_prefill)
        return self._jits[key]

    def _chunk_fn(self, n_tokens: int):
        """Fused decode executable for a chunk of ``n_tokens`` steps; the
        engine cache is donated (arg 1), so the KV rings update in place."""
        key = ("chunk", n_tokens, self.max_len, self.greedy,
               "paged" if self.paged else "dense", self._replicas)
        if key not in self._jits:
            m, ml, greedy = self.model, self.max_len, self.greedy

            def decode_chunk(params, cache, state):
                return m.decode_chunk(params, cache, state, n_tokens,
                                      max_len=ml, greedy=greedy)
            self._jits[key] = self._jit(decode_chunk, donate_argnums=(1,))
        return self._jits[key]

    def _insert_rows(self, src_cache: Any, slot_ids: list[int]) -> None:
        """Scatter prefill cache rows into their slots via the cache
        backend (dense: moveaxis row scatter; paged: block-table scatter).
        The engine cache is donated into the jitted scatter either way,
        so admission updates the cache in place too."""
        self.cache_backend.insert(src_cache, slot_ids)

    # ------------------------------------------------------------------
    def _admit_key(self, req: Request):
        """Requests sharing a key can prefill as one padded batch."""
        plen = len(req.prompt)
        bl = _bucket(plen) if self._pad_ok else plen
        return (bl, tuple(sorted(req.extras)))

    def _take_bucket(self, n_free: int) -> list[Request]:
        """Pop the head request plus every queued request in its bucket
        (preserving queue order of the rest), up to ``n_free``."""
        key = self._admit_key(self.queue[0])
        take: list[Request] = []
        rest: deque[Request] = deque()
        while self.queue and len(take) < n_free:
            r = self.queue.popleft()
            (take if self._admit_key(r) == key else rest).append(r)
        rest.extend(self.queue)
        self.queue = rest
        return take

    def _admit(self) -> None:
        with TraceAnnotation("engine.admit"):
            if self.paged:
                self._admit_paged()
                return
            free = [i for i, s in enumerate(self.slots) if not s.active]
            while free and self.queue:
                reqs = (self._take_bucket(len(free)) if self.batch_admit
                        else [self.queue.popleft()])
                slot_ids = [free.pop(0) for _ in reqs]
                self._admit_batch(slot_ids, reqs)

    def _cache_tokens(self, req: Request) -> int:
        """Cache positions a request can ever touch: vision prefix +
        prompt + decoded tokens, clamped to the horizon (decode stops at
        max_len - 1 regardless of budget)."""
        nv = self.model.cfg.n_vision_tokens or 0
        return min(nv + len(req.prompt) + req.max_new_tokens, self.max_len)

    def _block_hashes(self, req: Request) -> list[bytes]:
        """Content hash per FULL prompt block: a chained blake2b over
        (vision-token count, extras, then each block's token ids), so a
        block hash commits to everything at and before it — equal hashes
        imply bit-identical cached K/V (prefill K/V is batch- and
        padding-invariant; the parity tests pin this)."""
        bs = self.config.block_size
        nv = self.model.cfg.n_vision_tokens or 0
        W = nv + len(req.prompt)
        seed = hashlib.blake2b(digest_size=16)
        seed.update(np.int64(nv).tobytes())
        for k in sorted(req.extras):
            seed.update(k.encode())
            seed.update(np.ascontiguousarray(
                np.asarray(req.extras[k])).tobytes())
        prev = seed.digest()
        prompt = np.ascontiguousarray(np.asarray(req.prompt), np.int32)
        out: list[bytes] = []
        for i in range(W // bs):
            hh = hashlib.blake2b(prev, digest_size=16)
            hh.update(prompt[max(i * bs - nv, 0):
                             max((i + 1) * bs - nv, 0)].tobytes())
            prev = hh.digest()
            out.append(prev)
        return out

    def _peek_plan(self, req: Request):
        """Sharing plan for one request: ``(H, hit_hashes, full_hashes)``
        where ``H`` is the prefix-hit token count. Capped one block below
        the prompt end (at least one residual token must run so the
        prefill sample exists) and zeroed when the hit would not cover
        the vision prefix (the suffix embed path is text-only)."""
        bs = self.config.block_size
        nv = self.model.cfg.n_vision_tokens or 0
        W = nv + len(req.prompt)
        full = self._block_hashes(req)
        hits = self.cache_backend.peek_hit_blocks(full)
        H = min(len(hits), (W - 1) // bs) * bs
        if H < nv:
            H = 0
        return H, full[:H // bs], full

    def _key_for(self, req: Request, plan):
        """Paged admit key: requests batch into one prefill dispatch only
        when their padded width matches — for prefix hits that is the
        SUFFIX bucket, and the hit length H is folded in so every row of
        a suffix batch shares one context width and rope offset (logits
        are batch-size-sensitive at the last ulp, so hit and miss
        requests must not share a dispatch)."""
        if plan is None or plan[0] == 0:
            return self._admit_key(req)
        nv = self.model.cfg.n_vision_tokens or 0
        n_sfx = nv + len(req.prompt) - plan[0]
        return (_bucket(n_sfx), tuple(sorted(req.extras)), plan[0])

    def _admit_paged(self) -> None:
        """Block-budget admission, strict FIFO and bucket-barrier-free:
        pop the queue head while a free row AND enough free blocks exist,
        batching the maximal run of consecutive heads that share an admit
        key (one padded prefill dispatch per run — padding here is
        COMPUTE-only; cache memory is reserved at the request's real
        token count, so ragged prompts pay no cache padding). A head that
        does not fit stops admission — no scanning past it for smaller
        requests, so nothing starves.

        A failed reservation only ends the round once no deferred free is
        left to reclaim: rows released DURING the round (an instant
        finish inside ``_admit_batch``, a racing cancel) park blocks in
        the backend's pending list, and refusing while those are
        reclaimable would stall admission a whole macro-step on a pool
        that actually has room (the ``can_admit`` deferred-free bug)."""
        cb = self.cache_backend
        cb.flush()   # scrub freed rows' tables, reclaim their blocks
        free = [i for i, s in enumerate(self.slots) if not s.active]
        while free and self.queue:
            head_plan = self._peek_plan(self.queue[0]) if self._share \
                else None
            key = self._key_for(self.queue[0], head_plan)
            take: list[Request] = []
            slot_ids: list[int] = []
            plans: list = []
            blocked: bool | str = False
            limit = len(free) if self.batch_admit else 1
            while self.queue and free and len(take) < limit:
                req = self.queue[0]
                plan = self._peek_plan(req) if self._share else None
                if self._key_for(req, plan) != key:
                    break
                if self.fault is not None and self.fault.refuse_alloc():
                    blocked = "fault"    # injected pool exhaustion
                    break
                hashes = plan[1] if plan is not None else ()
                if not cb.alloc(free[0], self._cache_tokens(req),
                                block_hashes=hashes):
                    blocked = True
                    break
                slot_ids.append(free.pop(0))
                take.append(self.queue.popleft())
                plans.append(plan)
            if take:
                self._admit_batch(slot_ids, take, plans)
            if blocked == "fault":
                return
            if blocked and not cb._pending:
                # genuinely exhausted: FIFO holds the head until a real
                # completion frees blocks
                return
            if not take and not blocked:
                return
            # an instant finish inside _admit_batch parks its row in the
            # backend's pending list; flush so the recomputed free list
            # only offers rows whose reservation is actually released
            if cb._pending:
                cb.flush()
            free = [i for i, s in enumerate(self.slots) if not s.active]

    def _admit_batch(self, slot_ids: list[int], reqs: list[Request],
                     plans: list | None = None) -> None:
        self.admitting = tuple(r.rid for r in reqs)
        n = len(reqs)
        nv = self.model.cfg.n_vision_tokens or 0
        H = plans[0][0] if plans and plans[0] is not None else 0
        bl = (_bucket(nv + len(reqs[0].prompt) - H) if H
              else self._admit_key(reqs[0])[0])
        with TraceAnnotation("engine.prefill", rows=n, bucket=bl,
                             hit_tokens=H):
            if H:
                # residual-suffix prefill: every row shares hit length H (in
                # the admit key), so one gathered context of width exactly H
                # serves the batch. Gather BEFORE insert — insert donates the
                # tree the gather reads.
                padded = np.zeros((n, bl), np.int32)
                logits_idx = np.zeros((n,), np.int32)
                for j, r in enumerate(reqs):
                    sfx = np.asarray(r.prompt)[H - nv:]
                    padded[j, :len(sfx)] = sfx
                    logits_idx[j] = len(sfx) - 1
                batch = {"tokens": jnp.asarray(padded)}
                ctx = self.cache_backend.gather_prefix(slot_ids, H)
                logits, src_cache = self._suffix_prefill_fn(n, bl, H)(
                    self.params, batch, ctx, jnp.asarray(logits_idx))
                self.cache_backend.insert(src_cache, slot_ids, offset=H)
                self.prefill_tokens_executed += sum(
                    nv + len(r.prompt) - H for r in reqs)
                self.prefix_hit_tokens_total += n * H
            else:
                padded = np.zeros((n, bl), np.int32)
                logits_idx = np.zeros((n,), np.int32)
                for j, r in enumerate(reqs):
                    plen = len(r.prompt)
                    padded[j, :plen] = r.prompt   # right-pad into the bucket
                    logits_idx[j] = nv + plen - 1
                batch = {"tokens": jnp.asarray(padded)}
                for k in reqs[0].extras:
                    batch[k] = jnp.asarray(np.stack([np.asarray(r.extras[k])
                                                     for r in reqs]))
                logits, src_cache = self._prefill_fn(n, bl)(
                    self.params, batch, jnp.asarray(logits_idx))
                self._insert_rows(src_cache, slot_ids)
                self.prefill_tokens_executed += sum(
                    nv + len(r.prompt) for r in reqs)
            if self._share and plans:
                # index the new rows' full prompt blocks (hit rows extend the
                # chain past their hit; already-indexed hashes are skipped)
                for i, pl in zip(slot_ids, plans):
                    self.cache_backend.register_prefix(i, pl[2])
            first = self._pick(logits)
        now = time.perf_counter()
        for j, (i, r) in enumerate(zip(slot_ids, reqs)):
            slot = self.slots[i]
            slot.active = True
            slot.rid = r.rid
            slot.pos = nv + len(r.prompt)     # next write position
            slot.prompt_len = len(r.prompt)
            slot.remaining = r.max_new_tokens - 1
            slot.generated = [int(first[j])]
            slot.started = now
            slot.deadline = self._deadline_abs.pop(r.rid, None)
            slot.hit_tokens = H
            self.tokens_generated += 1
            # the prefill sample is the request's first streamed chunk —
            # its arrival is the time-to-first-chunk the Router windows
            self._emit_chunk(r.rid, (int(first[j]),), now)
        self.admitting = ()
        self.peak_active = max(self.peak_active,
                               sum(1 for s in self.slots if s.active))
        for i in slot_ids:
            if self.slots[i].active and self.slots[i].remaining <= 0:
                self._finish(i)

    def _pick(self, logits: jax.Array) -> np.ndarray:
        if self.greedy:
            return np.asarray(jnp.argmax(logits, axis=-1))
        self._key, sub = jax.random.split(self._key)
        return np.asarray(jax.random.categorical(sub, logits))

    def cancel(self, rid: int) -> bool:
        """Remove a request from the engine — queued or mid-decode — and
        free its cache reservation (paged: via the deferred
        ``CacheBackend.free``/``flush`` path, so block conservation is
        exact). Emits NO event: the canceller (Router deadline/retry
        logic, or an explicit backend ``cancel``) owns the request's
        terminal event. Returns whether the request was found."""
        self._deadline_abs.pop(rid, None)
        for r in self.queue:
            if r.rid == rid:
                self.queue = deque(q for q in self.queue if q.rid != rid)
                return True
        for i, s in enumerate(self.slots):
            if s.active and s.rid == rid:
                self.cache_backend.free(i)
                self.slots[i] = _Slot()
                return True
        return False

    def _expire_deadlines(self) -> None:
        """Cancel every queued/active request whose deadline passed,
        emitting a typed FailedEvent per expiry. Runs at the top of each
        step, so expiry frees slots and paged blocks before admission
        (the freed blocks are reclaimed by the admission flush)."""
        now = time.perf_counter()
        if self._deadline_abs:
            expired = {rid for rid, t in self._deadline_abs.items()
                       if now > t}
            if expired:
                self.queue = deque(r for r in self.queue
                                   if r.rid not in expired)
                for rid in expired:
                    del self._deadline_abs[rid]
                    self._emit_fail(rid, "deadline",
                                    "deadline expired while queued", now)
        for i, s in enumerate(self.slots):
            if s.active and s.deadline is not None and now > s.deadline:
                self._emit_fail(s.rid, "deadline",
                                f"deadline expired mid-decode after "
                                f"{len(s.generated)} tokens", now)
                self.cache_backend.free(i)
                self.slots[i] = _Slot()

    def _finish(self, i: int) -> None:
        s = self.slots[i]
        # prompt_len recorded at admission: s.pos here is prompt length
        # PLUS generated tokens (plus n_vision_tokens), not the prompt
        now = time.perf_counter()
        comp = Completion(s.rid, s.generated, s.prompt_len, now - s.started,
                          prefix_hit_tokens=s.hit_tokens)
        self.done.append(comp)
        self._emit_done(comp, now)
        # release the row's cache reservation (paged: deferred until the
        # next admission flush so the device table is scrubbed first)
        self.cache_backend.free(i)
        self.slots[i] = _Slot()

    # ------------------------------------------------------------------
    def _decode_chunk(self, active: list[int]) -> None:
        """One fused macro-step: decode up to ``chunk_tokens`` tokens for
        every active slot in a single dispatch, then materialise the token
        block with a single host transfer."""
        exact = max(1, min(
            self.chunk_tokens,
            min(self.slots[i].remaining for i in active),
            min(self.max_len - 1 - self.slots[i].pos for i in active)))
        # round down to a power of two: still never a scan iteration past
        # the shortest remaining budget, but the shared jit cache compiles
        # at most log2(max_chunk) scan lengths instead of one per distinct
        # clamp value (ragged budgets would otherwise trigger a compile
        # spike mid-serving on each new length)
        n_tokens = 1 << (exact.bit_length() - 1)
        with TraceAnnotation("engine.decode", rows=len(active),
                             tokens=n_tokens):
            n_rows = len(self.slots)
            tok = np.zeros((n_rows,), np.int32)
            pos = np.zeros((n_rows,), np.int32)
            rem = np.zeros((n_rows,), np.int32)
            act = np.zeros((n_rows,), bool)
            for i in active:
                s = self.slots[i]
                tok[i], pos[i], rem[i], act[i] = (s.generated[-1], s.pos,
                                                  s.remaining, True)
            state = {"tokens": jnp.asarray(tok), "pos": jnp.asarray(pos),
                     "remaining": jnp.asarray(rem),
                     "active": jnp.asarray(act), "key": self._key}
            block, emitted, state, self.cache = self._chunk_fn(n_tokens)(
                self.params, self.cache, state)
            self._key = state["key"]
        with TraceAnnotation("engine.readback"):
            block, emitted, moe = jax.device_get(
                (block, emitted, state.get("moe_counts")))
        if moe is not None:
            self.moe_held_assignments += int(moe[0])
            self.moe_dropped_assignments += int(moe[1])
        now = time.perf_counter()
        with TraceAnnotation("engine.emit"):
            for i in active:
                s = self.slots[i]
                c = int(emitted[i])
                new = block[i, :c].tolist()
                s.generated.extend(new)
                s.pos += c
                s.remaining -= c
                self.tokens_generated += c
                if new:
                    # one ChunkEvent per request per macro-step, built from
                    # the block that the single host transfer above already
                    # materialised — streaming costs no extra syncs
                    self._emit_chunk(s.rid, new, now)
                if s.remaining <= 0 or s.pos >= self.max_len - 1:
                    self._finish(i)
        self.chunks += 1

    def _decode_token(self, active: list[int]) -> None:
        """Per-token baseline path: one dispatch + one host sync per
        generated token, undonated cache (full copy per step) — kept so
        the fused path's win stays measurable (benchmarks)."""
        n_rows = len(self.slots)
        tokens = np.zeros((n_rows, 1), np.int32)
        pos = np.zeros((n_rows,), np.int32)
        for i in active:
            s = self.slots[i]
            tokens[i, 0] = s.generated[-1]
            pos[i] = s.pos
        logits, self.cache = self._decode(
            self.params, jnp.asarray(tokens), self.cache, jnp.asarray(pos))
        nxt = self._pick(logits)
        now = time.perf_counter()
        for i in active:
            s = self.slots[i]
            s.generated.append(int(nxt[i]))
            s.pos += 1
            s.remaining -= 1
            self.tokens_generated += 1
            self._emit_chunk(s.rid, (int(nxt[i]),), now)
            if s.remaining <= 0 or s.pos >= self.max_len - 1:
                self._finish(i)

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine macro-iteration: admit new requests, then one decode
        chunk (or one decode step in per-token mode). Returns whether the
        engine still has work (so pools can drive many engines round-robin
        without blocking on any one of them). Every call that found work —
        including admit-only ones — counts against ``run``'s budget."""
        if not self.has_work:
            return False
        with TraceAnnotation("engine.step"):
            self.steps += 1
            if self.fault is not None:
                self.fault.on_step(self.steps)   # may raise InjectedFault
            t0 = time.perf_counter()
            self._expire_deadlines()
            self._admit()
            active = [i for i, s in enumerate(self.slots) if s.active]
            if active:
                if self.chunked:
                    self._decode_chunk(active)
                else:
                    self._decode_token(active)
            self.busy_s += time.perf_counter() - t0
        return self.has_work

    def run(self, max_steps: int = 10_000) -> list[Completion]:
        """Drive until idle (or ``max_steps`` ``step()`` calls *for this
        call* — every call counts, so admit-only iterations cannot spin
        past the budget) and drain the finished completions — engines are
        reused across serves by the pool, so neither the step budget nor
        the done list may accumulate across calls.

        Exhausting the budget with work still queued is flagged loudly
        (``budget_exhausted`` plus a RuntimeWarning) instead of silently
        returning a partial wave — callers that batch-serve would
        otherwise drop the stragglers without any signal."""
        start = self.steps
        while self.has_work and self.steps - start < max_steps:
            self.step()
        self.budget_exhausted = self.has_work
        if self.budget_exhausted:
            n_active = sum(1 for s in self.slots if s.active)
            warnings.warn(
                f"ServingEngine.run() exhausted max_steps={max_steps} with "
                f"{len(self.queue)} queued and {n_active} active requests "
                "remaining; returning partial completions "
                "(engine.budget_exhausted is set)", RuntimeWarning,
                stacklevel=2)
        out, self.done = self.done, []
        return out
