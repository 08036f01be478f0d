"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles each kernel
at qwen3-0.6b widths (16 query / 8 kv heads, head_dim 128, max_len 512,
the prompt buckets the engine serves), and the paged decode program at
the benchmark cell's shapes, for one chip of a ``v5e:2x2`` topology
that is described, not attached. This is what interpret-mode
tests cannot show: the TPU lowering refuses block shapes that the
interpreter accepts. The topology is described inside a fixture, never
at import, and every test that needs it lives in this one file: one
process at a time may load the TPU library, and under pytest-xdist only
the worker given this file should.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

H, HKV, HD, MAX_LEN = 16, 8, 128, 512          # qwen3-0.6b attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compiles_to_kernel(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq", [16, 128])
def test_flash_attention_compiles(one_chip, seq):
    from repro.kernels.flash_attention import flash_attention
    f32 = jnp.float32
    _compiles_to_kernel(one_chip, flash_attention, ((2, seq, H, HD), f32),
                        ((2, seq, HKV, HD), f32), ((2, seq, HKV, HD), f32))


@pytest.mark.parametrize("batch,dtype", [(2, jnp.float32),
                                         (2, jnp.bfloat16),
                                         (8, jnp.float32)])
def test_decode_attention_compiles(one_chip, batch, dtype):
    """The engine's dense default (2 slots, max_len 512): refused by the
    TPU lowering while the mask rode as a (1, block_k) block."""
    from repro.kernels.decode_attention import decode_attention
    _compiles_to_kernel(one_chip, decode_attention, ((batch, H, HD), dtype),
                        ((batch, MAX_LEN, HKV, HD), dtype),
                        ((batch, MAX_LEN, HKV, HD), dtype),
                        ((batch, MAX_LEN), jnp.bool_))


def test_decode_attention_int8_compiles(one_chip):
    from repro.kernels.decode_attention import decode_attention_int8
    f32, i8 = jnp.float32, jnp.int8
    _compiles_to_kernel(one_chip, decode_attention_int8, ((2, H, HD), f32),
                        ((2, MAX_LEN, HKV, HD), i8),
                        ((2, MAX_LEN, HKV, HD), i8),
                        ((2, MAX_LEN), jnp.bool_),
                        ((2, MAX_LEN, HKV), f32), ((2, MAX_LEN, HKV), f32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_compiles(one_chip, dtype):
    """The paged default: 2 slots × 512 / 16 = 64 pages (+ scratch) and
    64 resident rows, block_size 16; a 28-layer pool read at a layer
    index."""
    from repro.kernels.paged_attention import paged_decode_attention
    i32 = jnp.int32
    _compiles_to_kernel(one_chip, paged_decode_attention,
                        ((64, H, HD), dtype), ((28, 65, 16, HKV * HD), dtype),
                        ((28, 65, 16, HKV * HD), dtype), ((64, 32), i32),
                        ((64,), i32), ((), i32))


def test_paged_decode_attention_head_major_compiles(one_chip):
    """stablelm-1.6b's 32 kv heads of 64: a 64-lane head block breaks
    the (8, 128) rule, so the kernel reads a head-major copy of the
    layer's pool."""
    from repro.kernels.paged_attention import paged_decode_attention
    bf16, i32 = jnp.bfloat16, jnp.int32
    _compiles_to_kernel(one_chip, paged_decode_attention,
                        ((64, 32, 64), bf16), ((24, 65, 16, 32 * 64), bf16),
                        ((24, 65, 16, 32 * 64), bf16), ((64, 32), i32),
                        ((64,), i32), ((), i32))


def test_paged_decode_attention_int8_compiles(one_chip):
    from repro.kernels.paged_attention import paged_decode_attention_int8
    f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32
    _compiles_to_kernel(one_chip, paged_decode_attention_int8,
                        ((64, H, HD), f32), ((28, 65, 16, HKV * HD), i8),
                        ((28, 65, 16, HKV * HD), i8),
                        ((28, 65, 16, HKV), f32), ((28, 65, 16, HKV), f32),
                        ((64, 32), i32), ((64,), i32), ((), i32))


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
# ops whose output is no new buffer: it aliases an operand or a carry
_ALIASING = {"parameter", "tuple", "get-tuple-element", "while", "bitcast"}


# in-place updates of their first operand, alone or as a fusion's root
_IN_PLACE = {"scatter", "dynamic-update-slice"}


def _large_outputs(hlo: str, limit: int) -> list[str]:
    """Instructions of an optimized HLO module, outside fused
    computations, whose output holds an array of ``limit`` bytes or
    more; ops that alias their input and in-place updates (a scatter or
    dynamic-update-slice, or a fusion whose root is one) are left out."""
    comps, roots, cur = {}, {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        if cur is not None:
            cur.append(line)
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    inst = re.compile(r"^\s*(ROOT\s+)?%(\S+)\s*=\s*(.*?)\s+([a-z][\w\-]*)\(")
    for name, lines in comps.items():
        for line in lines:
            m = inst.match(line)
            if m and m.group(1):
                roots[name] = m.group(4)
    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = inst.match(line)
            if not m or m.group(4) in _ALIASING | _IN_PLACE:
                continue
            called = re.search(r"calls=%([\w.\-]+)", line)
            if (m.group(4) == "fusion" and called
                    and roots.get(called.group(1)) in _IN_PLACE):
                continue
            for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                       m.group(3)):
                size = _DTYPE_BYTES.get(dt, 0)
                for d in filter(None, dims.split(",")):
                    size *= int(d)
                if size >= limit:
                    found.append(f"{m.group(4)} %{m.group(2)}: "
                                 f"{dt}[{dims}]")
    return found


# the qwen3-0.6b.chat cell (bench/configs/qwen3-0.6b.json): 72 rows over
# 460 pages of 128 tokens plus the scratch page, max_len 2048, bf16
CELL_ROWS, CELL_MAX_LEN, CELL_BLOCK, CELL_PAGES = 72, 2048, 128, 460


def _cell_cache(sharding):
    """qwen3-0.6b and its paged cache at the cell's shapes, as shape
    structs on the described chip, and one layer's pool in bytes."""
    from repro.configs.registry import get_config
    from repro.models.cache import PagedLayout
    from repro.models.model import Model
    model = Model(get_config("qwen3-0.6b"))
    layout = PagedLayout(CELL_BLOCK, CELL_PAGES)
    cache = _on(sharding, jax.eval_shape(lambda: model.init_cache(
        CELL_ROWS, CELL_MAX_LEN, jnp.bfloat16, layout=layout)))
    pool = cache["stack"]["k_pages"]
    assert pool.shape == (28, CELL_PAGES + 1, CELL_BLOCK, 8 * 128)
    return model, layout, cache, pool.size // 28 * pool.dtype.itemsize


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _assert_pool_untouched(compiled, layer_pool):
    assert _large_outputs(compiled.as_text(), layer_pool) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("n_tokens", [1, 4])
def test_paged_decode_chunk_updates_pool_in_place(one_chip, monkeypatch,
                                                  n_tokens):
    """qwen3-0.6b's decode chunk at the qwen3-0.6b.chat cell's shapes
    (bench/configs/qwen3-0.6b.json: 28 layers, 72 rows, 460 pages of
    128 tokens plus the scratch page, bf16, the cache donated), with the
    Pallas kernel as on the chip. The pools ride the layer scan's carry
    and the kernel reads them where they lie, so no instruction but the
    parameters and the in-place token scatter outputs an array of one
    layer's pool or more, and the temporaries stay far below one pool
    (they were 7.49 GB while the layer scan sliced and restacked it and
    the kernel transposed it). One token is the cell's chunk."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_mode", lambda: "tpu")
    model, _, cache, layer_pool = _cell_cache(one_chip)
    i32 = jnp.int32
    params = _on(one_chip, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16)))
    state = _on(one_chip, {
        "tokens": jax.ShapeDtypeStruct((CELL_ROWS,), i32),
        "pos": jax.ShapeDtypeStruct((CELL_ROWS,), i32),
        "remaining": jax.ShapeDtypeStruct((CELL_ROWS,), i32),
        "active": jax.ShapeDtypeStruct((CELL_ROWS,), jnp.bool_),
        "key": jax.eval_shape(lambda: jax.random.PRNGKey(0))})

    def decode_chunk(params, cache, state):
        return model.decode_chunk(params, cache, state, n_tokens,
                                  max_len=CELL_MAX_LEN)
    compiled = jax.jit(decode_chunk, donate_argnums=(1,)).lower(
        params, cache, state).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_pool_untouched(compiled, layer_pool)


def test_paged_cache_programs_update_pool_in_place(one_chip):
    """Every cache program that touches pages, at the same cell shapes:
    the prefill insert (a 2048-wide dense mini-cache row scattered into
    its pages), the prefix gather, the copy-on-write page copy, the
    table scrub and the table write. Each reads or updates the pool in
    place; a scatter or gather batched over the layers made the TPU
    compiler relayout the whole pool, one copy each way."""
    from repro.serving.cache import PagedCache
    model, layout, cache, layer_pool = _cell_cache(one_chip)
    one = jax.eval_shape(lambda: model.init_cache(1, CELL_MAX_LEN,
                                                  jnp.bfloat16))
    two = jax.eval_shape(lambda: model.init_cache(2, CELL_MAX_LEN,
                                                  jnp.bfloat16))
    axes = jax.tree.map(lambda a, b: next(
        i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y),
        one, two)
    cb = PagedCache(cache, CELL_ROWS, layout, CELL_MAX_LEN, axes, {},
                    prefix_cache=True)
    nblk = CELL_MAX_LEN // CELL_BLOCK

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    programs = [
        (cb._insert_fn(), (cache, _on(one_chip, one), i32(1), i32(1, nblk),
                           i32())),
        (cb._gather_fn(), (cache, i32(1, nblk), i32(512))),
        (cb._copy_fn(), (cache, i32(), i32())),
        (cb._clear_fn(), (cache, i32(1))),
        (cb._append_fn(), (cache, i32(), i32(1), i32(1))),
    ]
    for fn, args in programs:
        _assert_pool_untouched(fn.lower(*args).compile(), layer_pool)


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm import rmsnorm
    f32 = jnp.float32
    _compiles_to_kernel(one_chip, rmsnorm, ((2, 128, 1024), f32),
                        ((1024,), f32))


def test_mla_decode_compiles(one_chip):
    """deepseek-v2-lite's latent decode: kv_lora_rank 512, rope dim 64."""
    from repro.kernels.mla_decode import mla_decode_ctx
    f32 = jnp.float32
    _compiles_to_kernel(
        one_chip, lambda a, b, c, d, e: mla_decode_ctx(a, b, c, d, e,
                                                       scale=0.07),
        ((2, 16, 512), f32), ((2, 16, 64), f32), ((2, MAX_LEN, 512), f32),
        ((2, MAX_LEN, 64), f32), ((2, MAX_LEN), jnp.bool_))


def test_ssd_scan_compiles(one_chip):
    """mamba2-2.7b's SSD scan: 80 heads of 64, state 128, chunk 256."""
    from repro.kernels.ssd_scan import ssd_scan
    f32 = jnp.float32
    _compiles_to_kernel(
        one_chip, lambda *a: ssd_scan(*a, chunk=256),
        ((1, 512, 80, 64), f32), ((1, 512, 80), f32), ((80,), f32),
        ((1, 512, 1, 128), f32), ((1, 512, 1, 128), f32), ((80,), f32))


# the deepseek-v2-lite.long-answers cell (bench/configs/deepseek-v2-lite.json)
def _deepseek_cell(sharding):
    """deepseek-v2-lite at its published widths with this chip's 8 held
    experts, its paged latent cache at the cell's shapes, as shape
    structs on the described chip, and the serving settings."""
    import json
    import pathlib
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench.models import deepseek_v2
    from repro.models.cache import PagedLayout
    from repro.models.model import Model
    cfg = json.loads((root / "bench" / "configs" /
                      "deepseek-v2-lite.json").read_text())
    s = cfg["serving"]
    model = Model(deepseek_v2.program_config(cfg, "deepseek-v2-lite"))
    params = _on(sharding, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16)))
    cache = _on(sharding, jax.eval_shape(lambda: model.init_cache(
        s["max_seqs"], s["max_len"], jnp.bfloat16,
        layout=PagedLayout(s["block_size"], s["max_blocks"]))))
    return model, params, cache, s


def _slabs(model) -> set[str]:
    """Shapes of one layer's held expert weights, as HLO prints them."""
    c = model.cfg
    n, d, f = c.n_held, c.d_model, c.moe_d_ff
    return {f"[{a}]" for a in (f"{n},{d},{f}", f"{n},{f},{d}",
                               f"1,{n},{d},{f}", f"1,{n},{f},{d}")}


def _no_expert_slab(text: str, model) -> None:
    """No instruction outputs one layer's held experts (a slice of the
    stack): the grouped matmul reads the whole stack where it lies."""
    slabs = _slabs(model)
    bad = [line.strip()[:160] for line in text.splitlines()
           if " = " in line and any(f"bf16{s}" in line.split(" = ", 1)[1]
                                    .split(" ", 1)[0] for s in slabs)]
    assert bad == []


def test_deepseek_decode_chunk_reads_pools_and_experts_in_place(
        one_chip, monkeypatch):
    """deepseek-v2-lite's decode chunk at the long-answers cell's shapes
    (256 rows, 1878 pages of 128 tokens plus scratch, bf16, the cache
    donated), Pallas kernels as on the chip. The latent pools ride the
    layer scan's carry and each layer's held experts are read where they
    lie in the stack: no instruction but the parameters, the in-place
    token scatters and the dense latent gather outputs a layer's latent
    pool or more. That gather (``ckv_pages[layer, table]``, every row's
    whole table: (rows·nblk, bs, kv_lora_rank), one in the dense layer's
    scan and one in the MoE stack's) is the one known exception, named
    here by op and size; a paged latent kernel would end it. The
    temporaries stay under 1 GB (0.69 GB when written)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_mode", lambda: "tpu")
    model, params, cache, s = _deepseek_cell(one_chip)
    rows, i32 = s["max_seqs"], jnp.int32
    state = _on(one_chip, {
        "tokens": jax.ShapeDtypeStruct((rows,), i32),
        "pos": jax.ShapeDtypeStruct((rows,), i32),
        "remaining": jax.ShapeDtypeStruct((rows,), i32),
        "active": jax.ShapeDtypeStruct((rows,), jnp.bool_),
        "key": jax.eval_shape(lambda: jax.random.PRNGKey(0))})

    def decode_chunk(params, cache, state):
        return model.decode_chunk(params, cache, state, 1,
                                  max_len=s["max_len"])
    compiled = jax.jit(decode_chunk, donate_argnums=(1,)).lower(
        params, cache, state).compile()
    text = compiled.as_text()
    for kernel in ("mla_decode_ctx", "moe_expert_gmm"):
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", text)
    pool = cache["stack"]["ckv_pages"]
    layer_pool = pool.size // pool.shape[0] * pool.dtype.itemsize
    nblk = s["max_len"] // s["block_size"]
    gather = (f"bf16[{rows * nblk},{s['block_size']},"
              f"{model.cfg.kv_lora_rank}]")
    found = _large_outputs(text, layer_pool)
    assert len(found) == 2 and all(
        f.startswith("fusion ") and f.endswith(gather) for f in found), found
    for f in found:
        name = f.split("%", 1)[1].split(":", 1)[0]
        line = next(x for x in text.splitlines()
                    if x.strip().startswith(f"%{name} = "))
        assert re.search(r'op_name="[^"]*/gather"', line), line[:200]
    _no_expert_slab(text, model)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_deepseek_prefill_reads_experts_in_place(one_chip, monkeypatch):
    """The engine's prefill program at the cell's widest bucket (512):
    the held experts are read where they lie, and its temporaries stay
    under 0.5 GB."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_mode", lambda: "tpu")
    model, params, _, s = _deepseek_cell(one_chip)

    def prefill(params, batch, idx):
        return model.prefill(params, batch,
                             model.init_cache(1, s["max_len"]),
                             logits_at=idx)
    compiled = jax.jit(prefill).lower(
        params,
        _on(one_chip, {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)}),
        _on(one_chip, jax.ShapeDtypeStruct((1,), jnp.int32))).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention", "moe_expert_gmm"):
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", text)
    _no_expert_slab(text, model)
    assert compiled.memory_analysis().temp_size_in_bytes < 5e8


def test_moe_expert_gmm_compiles(one_chip):
    """The grouped matmul at the cell's decode shape: 256 rows' top-6
    assignments in tiles of 128 (12 tiles, plus one of padding per held
    expert), over the 26-layer stack of 8 held experts of 2048 × 1408."""
    from repro.kernels.moe_gmm import moe_expert_gmm
    bf16, i32 = jnp.bfloat16, jnp.int32
    _compiles_to_kernel(one_chip, moe_expert_gmm, ((20 * 128, 2048), bf16),
                        ((20,), i32), ((), i32), ((), i32),
                        ((26, 8, 2048, 1408), bf16),
                        ((26, 8, 2048, 1408), bf16),
                        ((26, 8, 1408, 2048), bf16))
