"""Paged decode attention: the Pallas block-table kernel (interpret mode)
and the pure-jnp paged reference, against the dense decode oracle.

The load-bearing property is *bit*-parity of the reference: gathering
K/V through a block table whose unreserved entries point at garbage
pages must produce the exact bits of dense decode attention — masked
lanes contribute an exact ``0.0`` to the flash accumulator (see
kernels/ref.paged_decode_attention), which is what lets the paged
serving engine bit-match the dense baseline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_int8)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


def _paged_case(key, B, H, Hkv, K, bs, nblk, n_pages, dtype,
                unique_pages=False, n_layers=None):
    """Random q + page pool ``(n_pages+1, bs, Hkv·K)`` (stacked
    ``(n_layers, …)`` when given) + block table + ragged lengths.
    Unreserved / beyond-length page contents are random garbage by
    construction."""
    ks = jax.random.split(key, 5)
    stack = () if n_layers is None else (n_layers,)
    q = jax.random.normal(ks[0], (B, H, K)).astype(dtype)
    k_pages = jax.random.normal(
        ks[1], stack + (n_pages + 1, bs, Hkv * K)).astype(dtype)
    v_pages = jax.random.normal(
        ks[2], stack + (n_pages + 1, bs, Hkv * K)).astype(dtype)
    if unique_pages:
        assert n_pages >= B * nblk
        perm = jax.random.permutation(ks[3], n_pages)[:B * nblk]
        table = perm.reshape(B, nblk).astype(jnp.int32)
    else:
        table = jax.random.randint(ks[3], (B, nblk), 0, n_pages, jnp.int32)
    lengths = jax.random.randint(ks[4], (B,), 1, bs * nblk + 1, jnp.int32)
    return q, k_pages, v_pages, table, lengths


def _quant(x, K):
    """Per-(token, kv head) absmax int8 over lane-flattened pages."""
    xh = x.reshape(x.shape[:-1] + (-1, K))
    scale = jnp.max(jnp.abs(xh), axis=-1) / 127.0
    qx = jnp.round(xh / scale[..., None]).astype(jnp.int8)
    return qx.reshape(x.shape), scale


@pytest.mark.parametrize("B,H,Hkv,K,bs,nblk", [
    (2, 4, 4, 64, 16, 4),     # MHA
    (3, 4, 2, 64, 16, 4),     # GQA
    (2, 8, 2, 32, 8, 6),      # small pages, more groups
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_ref(B, H, Hkv, K, bs, nblk, dtype):
    q, kp, vp, table, lengths = _paged_case(
        jax.random.PRNGKey(0), B, H, Hkv, K, bs, nblk, 32, dtype)
    got = paged_decode_attention(q, kp, vp, table, lengths, interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, table, lengths)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("K", [128, 64])      # lane block / head-major
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("layer", ["first", "last"])
def test_paged_kernel_stacked_pool(K, dtype, layer):
    """The kernel reads one layer of a stacked ``(L, P+1, bs, Hkv·K)``
    pool through the prefetched layer index, as the decode scan hands it
    over: first and last layer, ragged lengths, table entries past each
    sequence's pages on the scratch page, and f32 / bf16 / int8 pages.
    K = 128 reads a head's lane block in place; K = 64 takes the
    head-major fallback."""
    L, B, H, Hkv, bs, nblk, P = 3, 3, 4, 2, 16, 4, 12
    fp = jnp.float32 if dtype == jnp.int8 else dtype
    q, kp, vp, table, lengths = _paged_case(
        jax.random.PRNGKey(5), B, H, Hkv, K, bs, nblk, P, fp,
        n_layers=L)
    live = -(-lengths // bs)                       # pages each row holds
    table = jnp.where(jnp.arange(nblk)[None, :] < live[:, None], table, P)
    lidx = 0 if layer == "first" else L - 1
    if dtype == jnp.int8:
        (kp, ksc), (vp, vsc) = _quant(kp, K), _quant(vp, K)
        got = paged_decode_attention_int8(q, kp, vp, ksc, vsc, table,
                                          lengths, lidx, interpret=True)
        want = ref.paged_decode_attention(q, kp, vp, table, lengths, lidx,
                                          k_scale_pages=ksc,
                                          v_scale_pages=vsc)
        # one layer alone reads the same as that layer of the stack
        alone = ref.paged_decode_attention(q, kp[lidx], vp[lidx], table,
                                           lengths, k_scale_pages=ksc[lidx],
                                           v_scale_pages=vsc[lidx])
    else:
        got = paged_decode_attention(q, kp, vp, table, lengths, lidx,
                                     interpret=True)
        want = ref.paged_decode_attention(q, kp, vp, table, lengths, lidx)
        alone = ref.paged_decode_attention(q, kp[lidx], vp[lidx], table,
                                           lengths)
    assert jnp.array_equal(want, alone)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=_tol(fp), rtol=_tol(fp))


def test_paged_kernel_softcap():
    q, kp, vp, table, lengths = _paged_case(
        jax.random.PRNGKey(1), 2, 4, 2, 64, 16, 4, 32, jnp.float32)
    got = paged_decode_attention(q, kp, vp, table, lengths, softcap=30.0,
                                 interpret=True)
    want = ref.paged_decode_attention(q, kp, vp, table, lengths,
                                      softcap=30.0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_kernel_int8():
    """int8 pages with per-(token, kv head) absmax scales, dequant inside
    the kernel grid, vs the paged reference's gather-then-dequant."""
    key = jax.random.PRNGKey(2)
    B, H, Hkv, K, bs, nblk, P = 2, 4, 2, 64, 16, 4, 32
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, K), jnp.float32)
    kf = jax.random.normal(ks[1], (P + 1, bs, Hkv * K), jnp.float32)
    vf = jax.random.normal(ks[2], (P + 1, bs, Hkv * K), jnp.float32)
    kq, ksc = _quant(kf, K)
    vq, vsc = _quant(vf, K)
    table = jax.random.randint(ks[3], (B, nblk), 0, P, jnp.int32)
    lengths = jax.random.randint(ks[4], (B,), 1, bs * nblk + 1, jnp.int32)
    got = paged_decode_attention_int8(q, kq, vq, ksc, vsc, table, lengths,
                                      interpret=True)
    want = ref.paged_decode_attention(q, kq, vq, table, lengths,
                                      k_scale_pages=ksc, v_scale_pages=vsc)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_ref_bitwise_matches_dense_gather():
    """BIT-parity of the reference: scatter a dense K/V into disjoint
    pages, leave every unreserved page as garbage — the paged path must
    produce the exact bits of dense decode attention over the gathered
    context, garbage and all."""
    key = jax.random.PRNGKey(3)
    B, H, Hkv, K, bs, nblk, P = 3, 4, 2, 32, 16, 4, 16
    W = bs * nblk
    q, kp, vp, table, lengths = _paged_case(
        key, B, H, Hkv, K, bs, nblk, P, jnp.float32, unique_pages=True)
    # dense view: the exact tokens the table points at
    k = kp[table].reshape(B, W, Hkv, K)
    v = vp[table].reshape(B, W, Hkv, K)
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    got = ref.paged_decode_attention(q, kp, vp, table, lengths)
    want = ref.decode_attention_blocked(q, k, v, valid)
    assert jnp.array_equal(got, want), "paged reference is not bit-exact"


def test_paged_ref_ignores_garbage_pages():
    """Poisoning every page the tables don't reference (including the
    scratch page) must not change a single output bit."""
    key = jax.random.PRNGKey(4)
    B, H, Hkv, K, bs, nblk, P = 2, 4, 4, 32, 8, 4, 24
    q, kp, vp, table, lengths = _paged_case(
        key, B, H, Hkv, K, bs, nblk, P, jnp.float32, unique_pages=True)
    base = ref.paged_decode_attention(q, kp, vp, table, lengths)
    used = np.unique(np.asarray(table))
    poison = np.ones(P + 1, bool)
    poison[used] = False
    kp2 = jnp.where(jnp.asarray(poison)[:, None, None],
                    jnp.full_like(kp, 1e9), kp)
    vp2 = jnp.where(jnp.asarray(poison)[:, None, None],
                    jnp.full_like(vp, -1e9), vp)
    got = ref.paged_decode_attention(q, kp2, vp2, table, lengths)
    assert jnp.array_equal(got, base)


# ---------------------------------------------------------------------------
# the decode step's token write, in place on the stacked pool
# ---------------------------------------------------------------------------
def _stacked_garbage_group(cfg, L, B, max_len, layout, key):
    """A stacked paged attention group, every page filled with garbage;
    row b owns its own pages, the last row holds none (scratch only)."""
    from repro.models.cache import init_paged_attn_cache
    group = init_paged_attn_cache(cfg, B, max_len, jnp.float32, layout)
    out = {}
    for kk, (name, a) in zip(jax.random.split(key, len(group)),
                             sorted(group.items())):
        if name == "table":
            continue
        shape = (L,) + a.shape
        out[name] = (jax.random.randint(kk, shape, -127, 128).astype(a.dtype)
                     if a.dtype == jnp.int8
                     else jax.random.uniform(kk, shape, a.dtype, 0.5, 2.0))
    nblk = group["table"].shape[1]
    table = np.full((B, nblk), layout.scratch_page, np.int32)
    for b in range(B - 1):
        table[b] = np.arange(b * nblk, (b + 1) * nblk)
    out["table"] = jnp.asarray(table)
    return out


def _written(old, new):
    """Set of (layer, page, offset) rows that differ."""
    diff = np.asarray(old != new)
    diff = diff.reshape(diff.shape[:3] + (-1,)).any(-1)
    return {tuple(int(i) for i in ix) for ix in np.argwhere(diff)}


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_decode_writes_one_row_per_sequence_in_place(kv_dtype):
    """attn_decode on a stacked pool writes each sequence's new K/V row
    at [layer, table[b, pos // bs], pos % bs] (an idle row's at the
    scratch page) and nothing else: every other row of that layer and
    every other layer's pages stay bitwise unchanged."""
    import dataclasses

    from repro.configs.registry import get_config
    from repro.models import attention as attn
    from repro.models.cache import PagedLayout
    cfg = dataclasses.replace(get_config("qwen3-0.6b-reduced"),
                              kv_cache_dtype=kv_dtype)
    L, B, max_len, layer = 3, 3, 64, 1
    layout = PagedLayout(block_size=16, max_blocks=8)
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    group = _stacked_garbage_group(cfg, L, B, max_len, layout, keys[0])
    p = attn.init_attn(keys[1], cfg, jnp.float32)
    x = jax.random.normal(keys[2], (B, 1, cfg.d_model))
    pos = jnp.array([5, 37, 20], jnp.int32)
    _, new = attn.attn_decode(p, cfg, x, {**group, "layer": layer}, pos)

    _, k, v = attn._qkv(p, cfg, x, pos[:, None])
    rows = {"k_pages": k[:, 0], "v_pages": v[:, 0]}
    if kv_dtype == "int8":
        (rows["k_pages"], rows["k_scale_pages"]), \
            (rows["v_pages"], rows["v_scale_pages"]) = \
            attn._quant_kv(k[:, 0]), attn._quant_kv(v[:, 0])
    bs = layout.block_size
    table = np.asarray(group["table"])
    pages = [int(table[b, int(pos[b]) // bs]) for b in range(B)]
    offs = [int(pos[b]) % bs for b in range(B)]
    assert pages[-1] == layout.scratch_page
    assert np.array_equal(np.asarray(new["table"]), table)
    for name, row in rows.items():
        assert _written(group[name], new[name]) == {
            (layer, pg, off) for pg, off in zip(pages, offs)}, name
        for b in range(B):
            got = np.asarray(new[name][layer, pages[b], offs[b]])
            assert np.array_equal(got, np.asarray(row[b]).reshape(got.shape))


def test_decode_step_writes_each_layer_once(reduced_models):
    """Through the model's layer scan (pools in the carry): one decode
    step writes exactly one row per sequence in every layer's pool, at
    that layer's index, and leaves every other page bitwise unchanged."""
    from repro.models.cache import PagedLayout
    model, params = reduced_models["qwen3-0.6b"]
    cfg = model.cfg
    L, B, max_len = cfg.n_layers, 3, 64
    layout = PagedLayout(block_size=16, max_blocks=8)
    group = _stacked_garbage_group(cfg, L, B, max_len, layout,
                                   jax.random.PRNGKey(7))
    cache = model.init_cache(B, max_len, layout=layout)
    stack = {**cache["stack"], **group,
             "table": jnp.broadcast_to(group["table"],
                                       cache["stack"]["table"].shape)}
    pos = jnp.array([3, 50, 17], jnp.int32)
    tokens = jnp.array([[1], [2], [3]], jnp.int32)
    _, new = model.decode_step(params, tokens, {"stack": stack}, pos)
    bs = layout.block_size
    table = np.asarray(group["table"])
    want = {(l, int(table[b, int(pos[b]) // bs]), int(pos[b]) % bs)
            for l in range(L) for b in range(B)}
    for name in ("k_pages", "v_pages"):
        assert new["stack"][name].shape == stack[name].shape
        assert _written(stack[name], new["stack"][name]) == want, name
