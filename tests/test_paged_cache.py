"""Paged KV cache behind the CacheBackend protocol: greedy bit-parity
with the dense baseline across every model family, block-budget
admission, the EngineConfig surface, and the curated public API.

Parity methodology: BOTH engines receive the SAME precomputed Request
lists (a shared rng between the two serves would silently hand them
different prompts and fail for the wrong reason). The paged engine is
deliberately run with its full block budget — it admits MORE requests
concurrently than ``n_slots`` (``peak_active`` asserts it) and must
still emit identical greedy streams per rid.
"""
from __future__ import annotations

import warnings

import numpy as np
import pytest

from repro.serving import EngineConfig
from repro.serving.cache import BlockAllocator, CacheBackend, PagedCache
from repro.serving.engine import (PROMPT_BUCKETS, Request, ServingEngine,
                                  _bucket)

# one representative per model family (see models/model.py's family table)
FAMILY_ARCHS = [
    "qwen3-0.6b",        # dense
    "gemma3-27b",        # gemma (local/global sliding-window pattern)
    "mixtral-8x22b",     # moe (GQA)
    "mamba2-2.7b",       # ssm
    "zamba2-7b",         # zamba (ssm + shared attention)
    "whisper-large-v3",  # whisper (encoder-decoder, cross-attention)
]

# ragged prompts around the block boundary (block_size=16: 15/16/17),
# ragged budgets so slots finish mid-chunk, a 2-token prompt, and
# enough requests that the paged engine's admission exceeds n_slots=2
SPEC = [(5, 4), (15, 3), (16, 5), (17, 2), (9, 6), (2, 1), (12, 8), (7, 5)]

DENSE = EngineConfig(n_slots=2, max_len=64)
PAGED = EngineConfig(n_slots=2, max_len=64, cache="paged", block_size=16)


def _requests(cfg, plens_max_new, seed=0):
    """Deterministic ragged requests; whisper/vlm extras attached. A
    fresh seeded rng per call: two calls build identical prompt lists."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (plen, max_new) in enumerate(plens_max_new):
        extras = {}
        if cfg.n_encoder_layers:
            extras["audio_frames"] = 0.1 * rng.standard_normal(
                (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        if cfg.n_vision_tokens:
            extras["vision_embeds"] = 0.1 * rng.standard_normal(
                (cfg.n_vision_tokens, cfg.vision_embed_dim)).astype(
                    np.float32)
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                       dtype=np.int32),
            max_new_tokens=max_new, extras=extras))
    return reqs


def _serve(model, params, reqs, config):
    eng = ServingEngine(model, params, config)
    eng.submit_many([Request(r.rid, r.prompt, r.max_new_tokens, r.extras)
                     for r in reqs])
    return {c.rid: c.tokens for c in eng.run()}, eng


# ---------------------------------------------------------------------------
# bit-parity across every family, in-flight beyond n_slots
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_matches_dense_greedy(arch, reduced_models):
    """Identical greedy token streams per rid, with the paged engine
    admitting MORE concurrent requests than the dense engine has slots —
    the cache layout (and the admission width it allows) must be
    semantically invisible."""
    model, params = reduced_models[arch]
    reqs = _requests(model.cfg, SPEC)
    want, _ = _serve(model, params, reqs, DENSE)
    got, eng = _serve(model, params, reqs, PAGED)
    assert got == want
    assert eng.peak_active > DENSE.n_slots, (
        "paged engine never exceeded the dense slot count — the "
        "block-budget admission isn't doing its job")


def test_paged_block_exhaustion_completes(reduced_models):
    """A block pool smaller than the workload: admission stalls on the
    queue head when the allocator runs dry (strict FIFO, no scan-past),
    frees blocks as requests finish, and still completes everything with
    dense-identical streams."""
    model, params = reduced_models["qwen3-0.6b"]
    tight = EngineConfig(n_slots=2, max_len=64, cache="paged",
                         block_size=16, max_blocks=3)
    reqs = _requests(model.cfg, [(16, 4), (16, 4), (16, 4), (5, 2)])
    want, _ = _serve(model, params, reqs, DENSE)
    got, eng = _serve(model, params, reqs, tight)
    assert got == want
    # ≤3 blocks: never more than one 2-block request resident at a time
    assert eng.peak_active <= 2
    # block conservation: free + held (incl. pending-release rows) = pool
    cb = eng.cache_backend
    assert cb.allocator.n_free + sum(len(b) for b in cb._blocks) == 3


def test_paged_respects_max_len_truncation(reduced_models):
    """Budgets past the horizon: both layouts clamp at max_len - 1 and
    stay bit-identical (the paged reservation is clamped too)."""
    model, params = reduced_models["qwen3-0.6b"]
    dense = EngineConfig(n_slots=2, max_len=32)
    paged = EngineConfig(n_slots=2, max_len=32, cache="paged",
                         block_size=16)
    reqs = _requests(model.cfg, [(8, 100), (30, 100), (17, 10)])
    want, _ = _serve(model, params, reqs, dense)
    got, _ = _serve(model, params, reqs, paged)
    assert got == want


# ---------------------------------------------------------------------------
# EngineConfig surface
# ---------------------------------------------------------------------------
def test_engine_legacy_kwargs_warn_and_forward(reduced_models):
    model, params = reduced_models["qwen3-0.6b"]
    with pytest.warns(DeprecationWarning, match="EngineConfig"):
        eng = ServingEngine(model, params, n_slots=3, max_len=32)
    assert eng.config == EngineConfig(n_slots=3, max_len=32)
    assert eng.n_slots == 3 and eng.max_len == 32


def test_engine_rejects_config_plus_legacy_kwargs(reduced_models):
    model, params = reduced_models["qwen3-0.6b"]
    with pytest.raises(TypeError, match="not both"):
        ServingEngine(model, params, EngineConfig(), n_slots=2)


def test_engine_config_validation():
    with pytest.raises(ValueError, match="dense.*paged|paged.*dense"):
        EngineConfig(cache="bogus")
    with pytest.raises(ValueError, match="multiple"):
        EngineConfig(cache="paged", max_len=60, block_size=16)
    with pytest.raises(ValueError, match="prefix_cache"):
        EngineConfig(prefix_cache=True)              # needs cache="paged"
    cfg = EngineConfig(n_slots=2, max_len=64, cache="paged", block_size=16)
    assert cfg.resolved_max_blocks == 8          # dense footprint default
    assert cfg.resolved_max_seqs == 8
    assert cfg.n_rows == 8
    assert EngineConfig(n_slots=2, max_len=64).n_rows == 2


def test_engine_cache_property_proxies_backend(reduced_models):
    """Both layouts satisfy the runtime-checkable CacheBackend protocol
    and the engine's ``cache`` attribute proxies the backend's tree."""
    model, params = reduced_models["qwen3-0.6b"]
    for cfg in (DENSE, PAGED):
        eng = ServingEngine(model, params, cfg)
        assert isinstance(eng.cache_backend, CacheBackend)
        assert eng.cache is eng.cache_backend.tree
    assert isinstance(eng.cache_backend, PagedCache)


# ---------------------------------------------------------------------------
# the hoisted bucket table (bugfix regression)
# ---------------------------------------------------------------------------
def test_bucket_table_single_definition():
    """Engine and router must share ONE bucket table — the historic bug
    was a second hardcoded tuple drifting out of sync."""
    import repro.serving.router as router_mod
    assert router_mod._bucket is _bucket
    assert PROMPT_BUCKETS[0] == 16 and PROMPT_BUCKETS == tuple(
        sorted(PROMPT_BUCKETS))
    for n, want in [(1, 16), (16, 16), (17, 32), (512, 512), (513, 1024),
                    (2048, 2048), (2049, 4096), (5000, 8192)]:
        assert _bucket(n) == want, (n, want)


# ---------------------------------------------------------------------------
# curated public surface + deprecation shims
# ---------------------------------------------------------------------------
def test_public_surface_is_curated():
    import repro.serving as s
    assert s.__all__ == ["Router", "Request", "Completion", "ChunkEvent",
                         "DoneEvent", "RetryEvent", "FailedEvent",
                         "RejectedEvent", "ContainerFailure",
                         "RequestFailed", "RequestRejected", "Fault",
                         "FaultPlan", "ContainerBackend", "EngineConfig",
                         "CacheBackend"]
    for name in s.__all__:
        assert getattr(s, name) is not None


def test_legacy_serving_import_warns():
    import repro.serving as s
    with pytest.warns(DeprecationWarning, match="repro.serving.pool"):
        assert s.ContainerServingPool is not None
    with pytest.raises(AttributeError):
        s.NoSuchName


def test_wave_shim_warns_once(reduced_models):
    import repro.serving.pool as pool_mod
    from repro.serving.backend import ThreadBackend
    from repro.serving.pool import ContainerServingPool
    model, params = reduced_models["qwen3-0.6b"]
    backend = ThreadBackend(model, params, 1, config=DENSE)
    pool = ContainerServingPool(model, params, 1, backend=backend)
    reqs = _requests(model.cfg, [(4, 1)])
    old = pool_mod._WAVE_SHIM_WARNED
    try:
        pool_mod._WAVE_SHIM_WARNED = False
        with pytest.warns(DeprecationWarning, match="Router.submit"):
            pool.serve_timed(reqs)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            pool.serve_timed(reqs)       # second wave: silent
    finally:
        pool_mod._WAVE_SHIM_WARNED = old


# ---------------------------------------------------------------------------
# allocator unit behaviour (the non-hypothesis half; properties live in
# test_block_allocator_props.py)
# ---------------------------------------------------------------------------
def test_block_allocator_all_or_nothing():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and a.n_free == 1
    assert a.alloc(2) is None and a.n_free == 1      # refused, untouched
    a.free(got)
    assert a.n_free == 4
    with pytest.raises(ValueError):
        a.free(got)                                  # double free
    with pytest.raises(ValueError):
        a.free([99])                                 # foreign block


# ---------------------------------------------------------------------------
# deferred-free reclamation at admission (bugfix regression)
# ---------------------------------------------------------------------------
def test_can_admit_counts_deferred_frees():
    """``can_admit`` must see blocks parked behind a deferred free: the
    pre-fix version counted only ``allocator.n_free``, so a freshly
    freed (but unflushed) row made a reclaimable pool look exhausted."""
    from repro.models.cache import PagedLayout
    layout = PagedLayout(block_size=4, max_blocks=4)
    cache = PagedCache(tree={}, n_rows=2, layout=layout, max_len=16,
                       batch_axes=None, jits={})
    assert cache.alloc(0, 16)                  # whole pool to row 0
    assert not cache.can_admit(4)              # live row: truly full
    cache.free(0)                              # deferred (awaiting flush)
    assert cache.allocator.n_free == 0         # nothing freed yet...
    assert cache.can_admit(16)                 # ...but all reclaimable
    cache.flush()
    assert cache.alloc(1, 16)


def test_admission_reclaims_deferred_frees_same_step(reduced_models):
    """Admit/finish churn on a pool exactly one request wide: each
    max_new_tokens=1 request instant-finishes inside the admission batch,
    parking its blocks behind a deferred free. The engine must flush and
    keep admitting within the SAME macro-step — pre-fix, the blocked
    round ended and each request cost a full step."""
    model, params = reduced_models["qwen3-0.6b"]
    tight = EngineConfig(n_slots=4, max_len=64, cache="paged",
                         block_size=16, max_blocks=4)
    reqs = _requests(model.cfg, [(48, 1), (48, 1), (48, 1)])
    eng = ServingEngine(model, params, tight)
    eng.submit_many(reqs)
    eng.step()
    assert len(eng.done) == 3, "churn did not drain in one macro-step"
    assert eng.steps == 1
    cb = eng.cache_backend
    cb.flush()
    assert cb.allocator.n_free == 4 and cb.n_live_blocks == 0


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing: bit parity + hit accounting
# ---------------------------------------------------------------------------
SHARE_PREFIX_LEN = 64                 # four full 16-token blocks
SHARE_PHASE1 = [(80, 4)]              # seeds the prefix index alone
SHARE_PHASE2 = [(72, 3), (70, 4), (75, 2)]   # mixed tails, same prefix
# ssm archs are never bucket-padded and their chunked prefill scan needs
# seq % 32 == 0 — same shared prefix, chunk-aligned prompt lengths
SHARE_PHASE1_SSM = [(96, 4)]
SHARE_PHASE2_SSM = [(96, 3), (96, 4), (96, 2)]


def _shared_prefix_requests(cfg, specs, rid0=0, seed=0):
    """Requests sharing one SHARE_PREFIX_LEN-token prompt prefix (and,
    for encoder/vlm archs, identical extras — the hash seed covers
    extras, so differing frontends must not alias)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, (SHARE_PREFIX_LEN,),
                          dtype=np.int32)
    extras = {}
    if cfg.n_encoder_layers:
        extras["audio_frames"] = 0.1 * rng.standard_normal(
            (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    if cfg.n_vision_tokens:
        extras["vision_embeds"] = 0.1 * rng.standard_normal(
            (cfg.n_vision_tokens, cfg.vision_embed_dim)).astype(np.float32)
    reqs = []
    for i, (plen, max_new) in enumerate(specs):
        tail = rng.integers(0, cfg.vocab_size, (plen - SHARE_PREFIX_LEN,),
                            dtype=np.int32)
        reqs.append(Request(rid=rid0 + i,
                            prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=max_new, extras=extras))
    return reqs


def _serve_phases(model, params, phases, config):
    """Drive the phases through ONE engine, draining between them — the
    second phase's admission then sees the first phase's prefix index,
    and both sharing modes admit the same prefill batch sizes (logits
    are batch-size-sensitive at the last ulp, so parity needs equal n)."""
    eng = ServingEngine(model, params, config)
    got = {}
    for reqs in phases:
        eng.submit_many([Request(r.rid, r.prompt.copy(), r.max_new_tokens,
                                 extras=r.extras) for r in reqs])
        for c in eng.run():
            got[c.rid] = (c.tokens, c.prefix_hit_tokens)
    return got, eng


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_prefix_sharing_bit_parity(arch, reduced_models):
    """Greedy streams with prefix sharing ON are bit-identical to the
    non-sharing paged path at the same block budget, across every model
    family. Eligible archs (the engine's sharing gate) must show hits on
    the second phase; gated archs must run with zero hits — sharing off
    in all but name."""
    model, params = reduced_models[arch]
    p1, p2 = ((SHARE_PHASE1_SSM, SHARE_PHASE2_SSM) if model.cfg.is_ssm
              else (SHARE_PHASE1, SHARE_PHASE2))
    phases = [_shared_prefix_requests(model.cfg, p1, rid0=0),
              _shared_prefix_requests(model.cfg, p2, rid0=10)]
    base = dict(n_slots=4, max_len=128, cache="paged", block_size=16)
    on, eng_on = _serve_phases(model, params, phases,
                               EngineConfig(prefix_cache=True, **base))
    off, eng_off = _serve_phases(model, params, phases,
                                 EngineConfig(prefix_cache=False, **base))
    assert {r: t for r, (t, _) in on.items()} \
        == {r: t for r, (t, _) in off.items()}
    assert all(h == 0 for _, h in off.values())
    assert eng_off.prefix_hit_tokens_total == 0
    if eng_on._share:
        # every phase-2 request hit the full shared prefix
        assert [h for r, (_, h) in sorted(on.items()) if r >= 10] \
            == [SHARE_PREFIX_LEN] * len(SHARE_PHASE2)
        assert eng_on.prefix_hit_tokens_total \
            == SHARE_PREFIX_LEN * len(SHARE_PHASE2)
        assert eng_on.prefill_tokens_executed \
            < eng_off.prefill_tokens_executed
    else:
        assert eng_on.prefix_hit_tokens_total == 0
    # conservation with the prefix index holding its own references
    cb = eng_on.cache_backend
    cb.flush()
    assert (cb.allocator.n_free + cb.n_live_blocks
            == cb.layout.max_blocks)


def test_prefix_sharing_covers_moe():
    """The six-family sweep only exercises the dense gate (mixtral ships
    a sliding window); a window-free mixtral variant pins the moe suffix
    path — dense-layer prologue included — to the same bit parity."""
    import dataclasses as dc

    import jax

    from repro.configs.registry import get_config
    from repro.models.model import Model
    cfg = dc.replace(get_config("mixtral-8x22b-reduced"), sliding_window=0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    phases = [_shared_prefix_requests(cfg, SHARE_PHASE1, rid0=0),
              _shared_prefix_requests(cfg, SHARE_PHASE2, rid0=10)]
    base = dict(n_slots=4, max_len=128, cache="paged", block_size=16)
    on, eng_on = _serve_phases(model, params, phases,
                               EngineConfig(prefix_cache=True, **base))
    off, _ = _serve_phases(model, params, phases,
                           EngineConfig(prefix_cache=False, **base))
    assert eng_on._share, "window-free moe should pass the sharing gate"
    assert {r: t for r, (t, _) in on.items()} \
        == {r: t for r, (t, _) in off.items()}
    assert eng_on.prefix_hit_tokens_total \
        == SHARE_PREFIX_LEN * len(SHARE_PHASE2)


# ---------------------------------------------------------------------------
# page-axis indexing on 3-trailing-dim page groups (MLA latents, int8
# scales). Regression: _copy_fn/gather_prefix derived the layer-stack
# depth from the PAGE array's rank (ndim - 4) — right for attention
# pages, off by one for MLA/scale groups, which turned the CoW page copy
# into a silent no-op (OOB updates drop) and the gather into a read of
# the wrong axis. The depth now comes from the table (always 2 trailing
# dims), matching insert/_clear_fn.
# ---------------------------------------------------------------------------
def _mla_paged_backend():
    import jax

    from repro.configs.registry import get_config
    from repro.models.model import Model
    cfg = get_config("deepseek-v2-lite-16b-reduced")
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, EngineConfig(
        n_slots=2, max_len=64, cache="paged", block_size=16))
    return eng.cache_backend


def _each_paged_group(tree):
    from repro.serving.cache import is_paged_group
    if isinstance(tree, dict) and is_paged_group(tree):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from _each_paged_group(v)


def test_paged_copy_reaches_mla_page_axis():
    import jax.numpy as jnp
    cb = _mla_paged_backend()
    src, dst = 3, 5
    tree = cb.tree
    # seed page `src` on every pageable leaf; MLA page arrays stack as
    # (L, P+1, bs, hd) so axis 1 is the physical page axis
    def seed(t):
        if isinstance(t, dict):
            out = {}
            for k, v in t.items():
                if k.endswith("_pages") and "table" in t:
                    out[k] = v.at[:, src].set(7.5)
                else:
                    out[k] = seed(v) if isinstance(v, dict) else v
            return out
        return t
    tree = seed(tree)
    copied = cb._copy_fn()(tree, jnp.int32(src), jnp.int32(dst))
    groups = list(_each_paged_group(copied))
    assert groups, "no paged groups found in the MLA tree"
    for g in groups:
        for k, v in g.items():
            if not k.endswith("_pages"):
                continue
            pages = np.asarray(v)
            assert (pages[:, dst] == 7.5).all(), \
                f"{k}: CoW page copy did not reach page {dst}"
            assert (pages[:, 0] == 0.0).all(), \
                f"{k}: copy touched an unrelated page"


def test_paged_gather_reads_mla_page_axis():
    import jax.numpy as jnp
    cb = _mla_paged_backend()
    bs = cb.layout.block_size
    page = 4
    tree = cb.tree
    def seed(t):
        if isinstance(t, dict):
            out = {}
            for k, v in t.items():
                if k.endswith("_pages") and "table" in t:
                    # position j within the page carries value j+1
                    ramp = jnp.arange(1, bs + 1, dtype=v.dtype)
                    shape = [1] * v.ndim
                    shape[2] = bs
                    out[k] = v.at[:, page].set(
                        ramp.reshape(shape)[:, 0])
                else:
                    out[k] = seed(v) if isinstance(v, dict) else v
            return out
        return t
    tree = seed(tree)
    table_rows = jnp.full((1, cb.tree_nblocks if hasattr(cb, "tree_nblocks")
                           else 4), page, jnp.int32)
    pos = jnp.arange(bs, dtype=jnp.int32)
    gathered = cb._gather_fn()(tree, table_rows, pos)
    leaves = [np.asarray(v) for g in _each_paged_group_out(gathered)
              for v in g.values()]
    assert leaves, "gather returned no page data"
    for arr in leaves:
        # every gathered position j must carry the seeded value j+1,
        # regardless of trailing rank
        flat = arr.reshape(arr.shape[:-1] + (-1,)) if arr.ndim else arr
        expect = np.arange(1, bs + 1)
        got = np.moveaxis(arr, 2, 0).reshape(bs, -1)
        assert (got == expect[:, None]).all(), \
            "gather read the wrong axis for a 3-trailing-dim page group"


def _each_paged_group_out(tree):
    """Gather output groups: dicts of arrays (no table)."""
    if isinstance(tree, dict) and tree and all(
            not isinstance(v, dict) for v in tree.values()):
        yield tree
    elif isinstance(tree, dict):
        for v in tree.values():
            yield from _each_paged_group_out(v)


# ---------------------------------------------------------------------------
# the same page-axis programs on attention pages, stored token-major with
# the kv heads in the lanes: (L, P+1, bs, Hkv·hd)
# ---------------------------------------------------------------------------
def _attn_paged_backend(kv_dtype="model"):
    import dataclasses as dc

    import jax

    from repro.configs.registry import get_config
    from repro.models.model import Model
    cfg = dc.replace(get_config("qwen3-0.6b-reduced"),
                     kv_cache_dtype=kv_dtype)
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, EngineConfig(
        n_slots=2, max_len=64, cache="paged", block_size=16))
    return eng.cache_backend, cfg


def _seed_pages(tree, key):
    """Every page leaf filled with distinct random values."""
    import jax
    import jax.numpy as jnp
    leaves, tdef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for i, (path, a) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if str(path[-1].key).endswith("_pages"):
            a = (jax.random.randint(k, a.shape, -127, 128).astype(a.dtype)
                 if a.dtype == jnp.int8
                 else jax.random.normal(k, a.shape).astype(a.dtype))
        out.append(a)
    return jax.tree_util.tree_unflatten(tdef, out)


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_paged_copy_moves_whole_attention_pages(kv_dtype):
    """Copy-on-write on attention pages: page ``dst`` of every layer
    becomes page ``src`` bit for bit (K/V lanes of every kv head, and
    the int8 scale pages), and no other page changes."""
    import jax
    import jax.numpy as jnp
    cb, cfg = _attn_paged_backend(kv_dtype)
    group = cb.tree["stack"]
    assert group["k_pages"].shape[-1] == cfg.n_kv_heads * cfg.head_dim
    src, dst = 3, 5
    tree = _seed_pages(cb.tree, jax.random.PRNGKey(8))
    before = jax.tree.map(np.asarray, tree)
    copied = cb._copy_fn()(tree, jnp.int32(src), jnp.int32(dst))
    names = [k for k in group if k.endswith("_pages")]
    assert len(names) == (4 if kv_dtype == "int8" else 2)
    for name in names:
        old, new = before["stack"][name], np.asarray(copied["stack"][name])
        assert np.array_equal(new[:, dst], old[:, src]), name
        keep = np.arange(old.shape[1]) != dst
        assert np.array_equal(new[:, keep], old[:, keep]), name


def test_paged_gather_reads_attention_pages():
    """The prefix gather reads positions through the table rows and
    returns them in the pages' token shape (L, n, S, Hkv·hd), across a
    page boundary and for rows that own different pages."""
    import jax
    import jax.numpy as jnp
    cb, cfg = _attn_paged_backend()
    bs = cb.layout.block_size
    tree = _seed_pages(cb.tree, jax.random.PRNGKey(9))
    table_rows = jnp.array([[2, 6, 1, 0], [7, 3, 4, 5]], jnp.int32)
    pos = jnp.arange(2 * bs - 3, dtype=jnp.int32)
    got = cb._gather_fn()(tree, table_rows, pos)["stack"]
    tr, p = np.asarray(table_rows), np.asarray(pos)
    for name, key in (("k_pages", "k"), ("v_pages", "v")):
        pages = np.asarray(tree["stack"][name])
        want = pages[:, tr[:, p // bs], p % bs]
        assert got[key].shape == (cfg.n_layers, 2, len(p),
                                  cfg.n_kv_heads * cfg.head_dim)
        assert np.array_equal(np.asarray(got[key]), want), name


def test_paged_insert_then_gather_round_trips_dense_rows():
    """A dense (L, B, W, Hkv, hd) prefill mini-cache inserted into the
    pages (the per-request side reshaped to Hkv·hd lanes) reads back
    from the prefix gather as the same values, one row per request."""
    import jax

    from repro.models.model import Model
    cb, cfg = _attn_paged_backend()
    W = 24                                    # 1.5 pages per row
    for row in (0, 1):
        assert cb.alloc(row, W)
    dense = jax.eval_shape(lambda: Model(cfg).init_cache(2, W))["stack"]
    keys = jax.random.split(jax.random.PRNGKey(10), 2)
    src = {"stack": {name: jax.random.normal(k, dense[name].shape)
                     for k, name in zip(keys, ("k", "v"))}}
    want = jax.tree.map(np.asarray, src)["stack"]
    cb.insert(src, [0, 1])
    got = cb.gather_prefix([0, 1], W)["stack"]
    for name in ("k", "v"):
        assert np.array_equal(
            np.asarray(got[name]),
            want[name].reshape(want[name].shape[:3] + (-1,))), name


def test_paged_insert_refuses_offset_inside_a_block():
    """The insert writes whole pages from ``offset`` on, so an offset
    inside a block (which a prefix hit never gives) is refused."""
    cb, _ = _attn_paged_backend()
    with pytest.raises(ValueError, match="block_size"):
        cb.insert({}, [0], offset=cb.layout.block_size // 2)
