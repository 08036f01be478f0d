"""Cache backends for the serving engine: dense slot rows or paged blocks.

``CacheBackend`` is the protocol the engine programs against — block
accounting (``alloc``/``append``/``free``), prefill row insertion
(``insert``) and the device tree itself (``view``). Two implementations:

* ``DenseCache`` — the original ``(n_slots, max_len)`` row layout, kept as
  the bit-parity baseline (same pattern as ``chunked=False``). alloc/free
  are no-ops: a row IS the reservation.
* ``PagedCache`` — block/paged layout (models/cache.py): a shared pool of
  ``max_blocks`` physical pages plus a per-row block table. Admission
  reserves ``ceil(tokens / block_size)`` blocks per request — the real
  token count, not a power-of-two bucket — so in-flight concurrency is
  bounded by the block budget, not by ``n_slots``.

Both backends own the HOST-side accounting only; the device tree flows
through the engine's jits (donated) and is re-attached via the ``tree``
attribute. Paged bookkeeping invariants:

* every table entry outside a row's live reservation points at the
  SCRATCH page (index ``max_blocks``), so lockstep decode writes for
  idle rows land in the sink instead of a live block;
* ``free`` defers: freed rows park in a pending list and their device
  table rows are cleared to scratch (one jitted scatter in ``flush``,
  called at the top of each admission round) BEFORE the blocks return to
  the allocator — otherwise a frozen row could scribble on a block that
  admission just handed to a new sequence.

Prefix sharing (``prefix_cache=True``): full prompt blocks are indexed
by content hash, and admission maps a request's leading blocks onto
cache hits — several rows' tables point at the SAME physical page, and
only the residual suffix runs prefill. The machinery:

* ``BlockAllocator`` is refcounted: ``alloc`` hands out fresh blocks at
  refcount 1, ``share`` bumps, ``release`` drops and returns whatever
  hit zero. Conservation becomes ``n_free + n_live == n_blocks`` where
  ``n_live`` counts DISTINCT allocated blocks (each once, however many
  refs it carries).
* the cache holds its OWN reference on every indexed block, so a hit
  block survives its registering row. Blocks whose only remaining
  reference is the cache's sit in an LRU; admission evicts from it
  under pressure BEFORE refusing (``_reserve``).
* a write into a block with refcount > 1 forks it copy-on-write
  (``append``/``_cow_fork``): fresh block, device page copy, table
  repoint — the other holders never observe the write. The engine's
  admission keeps hits strictly below the first written position, so
  the fork is a defensive invariant (property-tested), not a hot path.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.cache import PagedLayout, is_paged_group


class BlockAllocator:
    """Refcounted free-list over ``n_blocks`` physical page indices.
    ``alloc`` is all-or-nothing (None when short — callers must not
    partially admit) and hands out blocks at refcount 1; ``share`` adds
    a reference to already-live blocks; ``release`` drops one reference
    per block and returns the blocks that reached zero (rejecting
    underflows and foreign indices). ``free`` is the historical alias
    for ``release`` — for the single-reference blocks the non-sharing
    engine deals in, they are the same operation."""

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free: list[int] = list(range(n_blocks))
        self._ref: dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        """Distinct allocated blocks — each counted ONCE regardless of
        how many references it carries, so ``n_free + n_live`` always
        equals ``n_blocks`` (the conservation the property tests pin)."""
        return len(self._ref)

    def ref(self, block: int) -> int:
        """Current reference count (0 for free/foreign blocks)."""
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> list[int] | None:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def share(self, blocks) -> None:
        """Add one reference to each of ``blocks`` (must be live)."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"share of unallocated block {b}")
        for b in blocks:
            self._ref[b] += 1

    def release(self, blocks) -> list[int]:
        """Drop one reference per block; blocks reaching zero return to
        the free list (and are reported back to the caller)."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"free of unallocated block {b}")
        freed = []
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)
                freed.append(b)
        return freed

    def free(self, blocks) -> None:
        self.release(blocks)


@runtime_checkable
class CacheBackend(Protocol):
    """What the serving engine needs from a KV-cache implementation."""
    n_rows: int
    tree: Any

    def can_admit(self, n_tokens: int) -> bool:
        """Could a ``n_tokens``-position reservation be satisfied once
        every reclaimable block (deferred frees awaiting ``flush``,
        evictable prefix-cache residents) is counted? A True here means
        the engine should flush/evict and retry rather than stall."""
        ...

    def alloc(self, row: int, n_tokens: int) -> bool:
        """Reserve cache space covering ``n_tokens`` positions for
        ``row``. False when the budget is short (the only side effect
        permitted on failure is evicting unreferenced cached blocks)."""
        ...

    def append(self, row: int, n_tokens: int = 1) -> bool:
        """Extend ``row``'s reservation by ``n_tokens`` positions."""
        ...

    def free(self, row: int) -> None:
        """Release ``row``'s reservation (may defer until ``flush``)."""
        ...

    def flush(self) -> None:
        """Make deferred frees effective (device table scrub included)."""
        ...

    def insert(self, src_cache: Any, rows: list[int],
               offset: int = 0) -> None:
        """Scatter a prefill mini-cache (dense layout, one row per admitted
        request) into the engine cache at ``rows``, starting at position
        ``offset`` (nonzero when a shared prefix already owns [0, offset))."""
        ...

    def view(self) -> Any:
        """The device cache tree the model consumes."""
        ...


# ---------------------------------------------------------------------------
# dense baseline
# ---------------------------------------------------------------------------
class DenseCache:
    """Row-per-slot baseline: capacity IS ``n_rows``, so block accounting
    degenerates to always-true and ``insert`` is the original moveaxis
    row scatter (donated, in place)."""

    def __init__(self, tree: Any, n_rows: int, batch_axes: Any, jits: dict):
        self.tree = tree
        self.n_rows = n_rows
        self._axes = batch_axes
        self._jits = jits

    def can_admit(self, n_tokens: int) -> bool:
        return True

    def alloc(self, row: int, n_tokens: int) -> bool:
        return True

    def append(self, row: int, n_tokens: int = 1) -> bool:
        return True

    def free(self, row: int) -> None:
        return

    def flush(self) -> None:
        return

    def _insert_fn(self):
        """Jitted row-scatter executable (donates the engine cache)."""
        key = ("insert", "dense")
        if key not in self._jits:
            axes = self._axes

            def insert_rows(cache, src, idx):
                def ins(e, s, ax):
                    if ax is None:
                        return e
                    em = jnp.moveaxis(e, ax, 0)
                    sm = jnp.moveaxis(s.astype(e.dtype), ax, 0)
                    return jnp.moveaxis(em.at[idx].set(sm), 0, ax)
                return jax.tree.map(ins, cache, src, axes)
            self._jits[key] = jax.jit(insert_rows, donate_argnums=(0,))
        return self._jits[key]

    def insert(self, src_cache: Any, rows: list[int],
               offset: int = 0) -> None:
        if offset:
            raise ValueError("DenseCache rows always start at position 0")
        self.tree = self._insert_fn()(self.tree, src_cache,
                                      jnp.asarray(rows))

    def view(self) -> Any:
        return self.tree


# ---------------------------------------------------------------------------
# paged backend
# ---------------------------------------------------------------------------
def _tree_has_paged_group(tree: Any) -> bool:
    if isinstance(tree, dict):
        if is_paged_group(tree):
            return True
        return any(_tree_has_paged_group(v) for v in tree.values())
    return False


# (pages key, dense prefill-cache key) pairs a paged group can hold
_PAGE_PAIRS = (("k_pages", "k"), ("v_pages", "v"),
               ("k_scale_pages", "k_scale"), ("v_scale_pages", "v_scale"),
               ("ckv_pages", "ckv"), ("k_rope_pages", "k_rope"))


class PagedCache:
    """Block-table cache backend. Host state: a refcounted allocator over
    the shared physical pages (ONE logical block spans every pageable
    layer — per-layer tables are replicas), per-row block lists, and —
    with ``prefix_cache`` — a content-hash index over full prompt blocks
    plus an LRU of cache-only residents."""

    def __init__(self, tree: Any, n_rows: int, layout: PagedLayout,
                 max_len: int, batch_axes: Any, jits: dict,
                 prefix_cache: bool = False):
        self.tree = tree
        self.n_rows = n_rows
        self.layout = layout
        self.max_len = max_len
        self._axes = batch_axes
        self._jits = jits
        self.allocator = BlockAllocator(layout.max_blocks)
        self._blocks: list[list[int]] = [[] for _ in range(n_rows)]
        self._tokens: list[int] = [0] * n_rows
        self._pending: list[int] = []          # rows freed, not yet scrubbed
        self._has_paged = _tree_has_paged_group(tree)
        self.prefix_cache = prefix_cache
        # content-hash index over full prompt blocks (both directions),
        # and the LRU of blocks whose ONLY reference is the cache's own
        # (oldest first — eviction order under admission pressure)
        self._hash_to_block: dict[bytes, int] = {}
        self._block_hash: dict[int, bytes] = {}
        self._lru: OrderedDict[int, None] = OrderedDict()
        self._hits: list[int] = [0] * n_rows   # prefix-hit tokens per row

    # -- accounting ----------------------------------------------------
    @property
    def n_live_blocks(self) -> int:
        """Distinct blocks currently held — by rows (pending-free rows
        included until ``flush``) or by the prefix index. At every point
        ``allocator.n_free + n_live_blocks == max_blocks`` — the exact
        conservation the chaos/cancellation tests assert."""
        held = {b for blocks in self._blocks for b in blocks}
        held.update(self._block_hash)
        return len(held)

    def hit_tokens(self, row: int) -> int:
        """Prompt positions of ``row`` satisfied by prefix-cache hits."""
        return self._hits[row]

    @property
    def n_cached_blocks(self) -> int:
        """Prefix-indexed blocks currently resident (shared or LRU)."""
        return len(self._block_hash)

    def _cap(self, n_tokens: int) -> int:
        return min(n_tokens, self.max_len)

    def can_admit(self, n_tokens: int) -> bool:
        # count every RECLAIMABLE block, not just the free list: blocks
        # parked behind a deferred free (rows in _pending) come back at
        # the next flush, and cache-only LRU residents are evictable —
        # only blocks held by live rows are truly unavailable
        pending = set(self._pending)
        held = {b for row, blocks in enumerate(self._blocks)
                if blocks and row not in pending
                for b in blocks}
        return (self.layout.max_blocks - len(held)
                >= self.layout.n_blocks(self._cap(n_tokens)))

    # -- prefix index ----------------------------------------------------
    def peek_hit_blocks(self, block_hashes) -> list[int]:
        """Longest indexed chain of leading prompt-block hashes. Purely
        a lookup — callers must alloc before the index can change."""
        hits: list[int] = []
        for h in block_hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            hits.append(b)
        return hits

    def register_prefix(self, row: int, block_hashes) -> None:
        """Index ``row``'s leading full prompt blocks by content hash.
        The cache takes its OWN reference on each newly indexed block so
        it outlives the row; hashes (or blocks) already indexed are
        skipped — a duplicate prompt admitted in the same cold batch
        keeps its private copy rather than aliasing after the fact."""
        if not self.prefix_cache:
            return
        blocks = self._blocks[row]
        for i, h in enumerate(block_hashes):
            if i >= len(blocks):
                break
            b = blocks[i]
            if h in self._hash_to_block or b in self._block_hash:
                continue
            self.allocator.share([b])
            self._hash_to_block[h] = b
            self._block_hash[b] = h

    def _evict(self, block: int) -> None:
        """Drop a cache-only resident: unindex and release the cache's
        reference (pages need no scrub — no live table points here, and
        attention never reads past a row's written positions)."""
        self._lru.pop(block)
        h = self._block_hash.pop(block)
        del self._hash_to_block[h]
        self.allocator.release([block])

    def _reserve(self, n: int, protect=()) -> bool:
        """Ensure ``n`` free blocks, evicting LRU residents (oldest
        first, never one in ``protect``) before giving up."""
        while self.allocator.n_free < n:
            victim = next((b for b in self._lru if b not in protect), None)
            if victim is None:
                return False
            self._evict(victim)
        return True

    # -- reservations ----------------------------------------------------
    def alloc(self, row: int, n_tokens: int, block_hashes=()) -> bool:
        """Reserve blocks covering ``n_tokens`` positions for ``row``.
        With ``block_hashes`` (leading full prompt-block hashes), the
        indexed prefix maps onto existing pages — the row SHARES them —
        and only the remainder draws fresh blocks. On pressure, LRU
        residents are evicted before refusing."""
        if self._blocks[row] or row in self._pending:
            raise ValueError(f"row {row} already holds a reservation")
        total = self.layout.n_blocks(self._cap(n_tokens))
        hits = (self.peek_hit_blocks(block_hashes)[:total]
                if self.prefix_cache else [])
        if not self._reserve(total - len(hits), protect=set(hits)):
            return False
        fresh = self.allocator.alloc(total - len(hits))
        if fresh is None:
            return False
        if hits:
            self.allocator.share(hits)
            for b in hits:
                self._lru.pop(b, None)     # row-referenced: not evictable
        self._blocks[row] = hits + fresh
        self._tokens[row] = self._cap(n_tokens)
        self._hits[row] = len(hits) * self.layout.block_size
        return True

    def append(self, row: int, n_tokens: int = 1) -> bool:
        old = self._tokens[row]
        new_total = old + n_tokens
        if new_total > self.max_len:
            return False
        # copy-on-write: positions [old, new_total) land in logical
        # blocks old//bs .. (new_total-1)//bs — fork any that are shared
        # (refcount > 1: another row, or the prefix index) before writing
        bs = self.layout.block_size
        for idx in range(old // bs,
                         min((new_total - 1) // bs + 1,
                             len(self._blocks[row]))):
            if (self.allocator.ref(self._blocks[row][idx]) > 1
                    and not self._cow_fork(row, idx)):
                return False
        need = (self.layout.n_blocks(new_total)
                - self.layout.n_blocks(old))
        if need > 0:
            if not self._reserve(need, protect=set(self._blocks[row])):
                return False
            blocks = self.allocator.alloc(need)
            if blocks is None:
                return False
            start = len(self._blocks[row])
            self._blocks[row].extend(blocks)
            if self._has_paged:
                self._write_table(row, start, blocks)
        self._tokens[row] = new_total
        return True

    def _cow_fork(self, row: int, idx: int) -> bool:
        """Give ``row`` a private copy of its shared logical block
        ``idx``: fresh block, device page copy, table repoint, then drop
        the row's reference on the original."""
        old = self._blocks[row][idx]
        if not self._reserve(1, protect=set(self._blocks[row])):
            return False
        fresh = self.allocator.alloc(1)
        if fresh is None:
            return False
        new = fresh[0]
        if self._has_paged:
            self.tree = self._copy_fn()(self.tree, jnp.int32(old),
                                        jnp.int32(new))
            self._write_table(row, idx, [new])
        self._blocks[row][idx] = new
        self.allocator.release([old])
        if old in self._block_hash and self.allocator.ref(old) == 1:
            self._lru[old] = None          # cache-only again: evictable
        return True

    def free(self, row: int) -> None:
        # idempotent: cancel/expire and completion may race to release
        # the same row (deadline expiry in the Router vs the engine
        # finishing the slot) — freeing an already-pending row twice
        # would double-free its blocks at the next flush
        if not self._blocks[row] or row in self._pending:
            return
        # deferred: the device table row must be scrubbed to scratch
        # before these blocks can be re-issued (see flush)
        self._pending.append(row)

    def flush(self) -> None:
        if not self._pending:
            return
        with TraceAnnotation("cache.flush"):
            rows, self._pending = self._pending, []
            if self._has_paged:
                self.tree = self._clear_fn()(self.tree,
                                             jnp.asarray(rows, jnp.int32))
            for row in rows:
                self.allocator.release(self._blocks[row])
                # indexed blocks survive on the cache's own reference;
                # once that is the LAST one they become LRU-evictable
                for b in self._blocks[row]:
                    if b in self._block_hash and self.allocator.ref(b) == 1:
                        self._lru[b] = None
                        self._lru.move_to_end(b)
                self._blocks[row] = []
                self._tokens[row] = 0
                self._hits[row] = 0

    # -- device-tree transforms ----------------------------------------
    def _table_rows(self, rows: list[int]) -> np.ndarray:
        nblk = self.max_len // self.layout.block_size
        out = np.full((len(rows), nblk), self.layout.scratch_page, np.int32)
        for j, row in enumerate(rows):
            blocks = self._blocks[row]
            out[j, :len(blocks)] = blocks
        return out

    def _clear_fn(self):
        key = ("paged_clear",)
        if key not in self._jits:
            scratch = self.layout.scratch_page

            def scrub_rows(t, rows):
                if isinstance(t, dict) and is_paged_group(t):
                    table = t["table"]
                    sdims = table.ndim - 2
                    tf = table.reshape((-1,) + table.shape[sdims:])
                    tf = tf.at[:, rows, :].set(scratch)
                    return {**t, "table": tf.reshape(table.shape)}
                if isinstance(t, dict):
                    return {k: scrub_rows(v, rows) for k, v in t.items()}
                return t

            self._jits[key] = jax.jit(scrub_rows, donate_argnums=(0,))
        return self._jits[key]

    def _copy_fn(self):
        """Physical page copy ``src -> dst`` across every pageable layer
        (the device half of a copy-on-write fork)."""
        key = ("paged_copy",)
        if key not in self._jits:
            def copy_page(t, src, dst):
                if isinstance(t, dict) and is_paged_group(t):
                    out = dict(t)
                    # stack depth from the TABLE (always 2 trailing
                    # dims); every page array is (P+1, bs, ·) under it
                    sdims = t["table"].ndim - 2
                    for dk, _ in _PAGE_PAIRS:
                        if dk not in t:
                            continue
                        pages = t[dk]
                        pf = pages.reshape((-1,) + pages.shape[sdims:])
                        pf = pf.at[:, dst].set(pf[:, src])
                        out[dk] = pf.reshape(pages.shape)
                    return out
                if isinstance(t, dict):
                    return {k: copy_page(v, src, dst) for k, v in t.items()}
                return t

            self._jits[key] = jax.jit(copy_page, donate_argnums=(0,))
        return self._jits[key]

    def _append_fn(self):
        """Jitted table-write executable (donates the tree): points a
        row's logical block indices at physical pages on device."""
        key = ("paged_append",)
        if key not in self._jits:
            def write_table(t, row_, idxs, pages):
                if isinstance(t, dict) and is_paged_group(t):
                    table = t["table"]
                    sdims = table.ndim - 2
                    tf = table.reshape((-1,) + table.shape[sdims:])
                    tf = tf.at[:, row_, idxs].set(pages)
                    return {**t, "table": tf.reshape(table.shape)}
                if isinstance(t, dict):
                    return {k: write_table(v, row_, idxs, pages)
                            for k, v in t.items()}
                return t

            self._jits[key] = jax.jit(write_table, donate_argnums=(0,))
        return self._jits[key]

    def _write_table(self, row: int, start: int, blocks: list[int]) -> None:
        """Point logical block indices [start, start+len) of ``row`` at
        ``blocks`` on device (append path — admission goes via insert)."""
        idxs = jnp.arange(start, start + len(blocks), dtype=jnp.int32)
        self.tree = self._append_fn()(self.tree, jnp.int32(row), idxs,
                                      jnp.asarray(blocks, jnp.int32))

    def _gather_fn(self):
        """Jitted prefix-gather executable — a pure READ, deliberately
        undonated (the tree must survive for the insert that follows)."""
        key = ("paged_gather",)
        if key not in self._jits:
            bs = self.layout.block_size

            def gather_prefix(t, table_rows, pos):
                if isinstance(t, dict) and is_paged_group(t):
                    out = {}
                    # table-derived stack depth, as in _copy_fn
                    sdims = t["table"].ndim - 2
                    for dk, sk in _PAGE_PAIRS:
                        if dk not in t:
                            continue
                        pages = t[dk]
                        pf = pages.reshape((-1,) + pages.shape[sdims:])
                        pp = table_rows[:, pos // bs]        # (n, H)
                        # (S, n, H, kv·hd), the layer indexed explicitly
                        # as in insert (a gather sliced over the layers
                        # makes the TPU compiler relayout the pool)
                        layer = jnp.arange(pf.shape[0])[:, None, None]
                        g = pf[layer, pp[None], (pos % bs)[None, None]]
                        out[sk] = g.reshape(pages.shape[:sdims]
                                            + g.shape[1:])
                    return out
                if isinstance(t, dict):
                    return {k: gather_prefix(v, table_rows, pos)
                            for k, v in t.items()}
                return None

            self._jits[key] = jax.jit(gather_prefix)
        return self._jits[key]

    def gather_prefix(self, rows: list[int], n_tokens: int) -> Any:
        """Read the first ``n_tokens`` cached positions of ``rows`` out
        of the paged pool as dense per-group K/V in the pages' token
        shape (``(…, n, n_tokens, Hkv·hd)`` for attention) — the
        attention context a suffix prefill consumes. Pure read (no
        donation): call BEFORE ``insert`` consumes the tree."""
        return self._gather_fn()(self.tree,
                                 jnp.asarray(self._table_rows(rows)),
                                 jnp.arange(n_tokens))

    def _insert_fn(self):
        """Jitted prefill-scatter executable (donates the tree)."""
        key = ("insert", "paged")
        if key not in self._jits:
            axes = self._axes
            scratch = self.layout.scratch_page

            def group_ins(dst, src, rows_, table_rows, offset_):
                out = dict(dst)
                table = dst["table"]
                sdims = table.ndim - 2
                tf = table.reshape((-1,) + table.shape[sdims:])
                tf = tf.at[:, rows_, :].set(table_rows[None])
                out["table"] = tf.reshape(table.shape)
                nblk = table_rows.shape[1]
                for dk, sk in _PAGE_PAIRS:
                    if dk not in dst:
                        continue
                    pages, s = dst[dk], src[sk]
                    bs = pages.shape[sdims + 1]
                    W = s.shape[sdims + 1]
                    nb = -(-W // bs)
                    # whole pages from ``offset`` (a block boundary) on:
                    # the tail past the prompt is garbage either way, and
                    # a page is one contiguous block of the pool where a
                    # token row is a strided sublane of its tiles
                    bi = offset_ // bs + jnp.arange(nb)
                    # offset + bucket padding can run past the table:
                    # clamp those blocks to the scratch sink (jax would
                    # silently clamp the gather to the LAST table entry
                    # — a live block — instead)
                    pp = jnp.where(bi[None, :] < nblk,
                                   table_rows[:, jnp.minimum(bi, nblk - 1)],
                                   scratch)
                    pf = pages.reshape((-1,) + pages.shape[sdims:])
                    # the per-request side takes the page's token shape
                    # ((W, Hkv, hd) -> (W, Hkv·hd)); the pool is not moved
                    sf = s.astype(pages.dtype).reshape(
                        (-1,) + s.shape[sdims:sdims + 2]
                        + pages.shape[sdims + 2:])
                    sf = jnp.pad(sf, [(0, 0), (0, 0), (0, nb * bs - W)]
                                 + [(0, 0)] * (sf.ndim - 3))
                    sf = sf.reshape(sf.shape[:2] + (nb, bs) + sf.shape[3:])
                    # the layer indexed explicitly: a scatter batched
                    # over the layers (vmap) makes the TPU compiler lay
                    # the whole pool out layer-minor, a copy each way
                    layer = jnp.arange(pf.shape[0])[:, None, None]
                    scat = pf.at[layer, pp[None]].set(sf)
                    out[dk] = scat.reshape(pages.shape)
                return out

            def walk(dst, src, ax, rows_, table_rows, offset_):
                if isinstance(dst, dict) and is_paged_group(dst):
                    return group_ins(dst, src, rows_, table_rows, offset_)
                if isinstance(dst, dict):
                    return {k: walk(dst[k], src[k], ax[k], rows_,
                                    table_rows, offset_) for k in dst}
                if ax is None:
                    return dst
                em = jnp.moveaxis(dst, ax, 0)
                sm = jnp.moveaxis(src.astype(dst.dtype), ax, 0)
                return jnp.moveaxis(em.at[rows_].set(sm), 0, ax)

            def insert_rows(tree, src, rows_, table_rows, offset_):
                return walk(tree, src, axes, rows_, table_rows, offset_)
            self._jits[key] = jax.jit(insert_rows, donate_argnums=(0,))
        return self._jits[key]

    def insert(self, src_cache: Any, rows: list[int],
               offset: int = 0) -> None:
        """Scatter the dense prefill mini-cache into the paged tree: every
        position of each source row lands at ``(table[p // bs], p % bs)``
        — positions beyond the row's reservation hit the scratch page, so
        bucket-padded prefill garbage goes to the sink, while live
        positions are copied verbatim (the bit-parity guarantee). A
        nonzero ``offset`` shifts the landing positions: the suffix path
        writes residual K/V behind ``offset`` shared-prefix positions,
        a whole number of blocks (hits are full blocks). Pages are
        written whole, so ``offset`` must be a block boundary."""
        if offset % self.layout.block_size:
            raise ValueError(f"offset {offset} is not a multiple of "
                             f"block_size {self.layout.block_size}")
        self.tree = self._insert_fn()(self.tree, src_cache,
                                      jnp.asarray(rows, jnp.int32),
                                      jnp.asarray(self._table_rows(rows)),
                                      jnp.int32(offset))

    def view(self) -> Any:
        return self.tree
