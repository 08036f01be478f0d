"""DeepSeek-V2's model file and the program at test widths on the CPU,
with the mechanisms of deepseek-v2-lite: latent attention with YaRN
rope (rope pairs interleaved in the published layout), one dense layer,
then routed experts of which this chip holds a share, beside shared
experts, gates not renormalised. The tiny configuration is
``data/tiny-deepseek.json``: 16 routed experts over 8 chips, 2 held."""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import readers, run, spec, traffic
from bench.models import deepseek_v2 as ds
from bench.models.common import check_program_tree, seed_key
from bench.trace import Event, Reduced

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "deepseek-v2-lite.long-answers"
TINY = json.loads((DATA / "tiny-deepseek.json").read_text())


def _share(cfg: dict, chip: int, n: int | None = None) -> dict:
    """The configuration of chip ``chip``'s share (``n`` experts each)."""
    n = n or cfg["n_routed_experts"]
    return {**cfg, "n_routed_experts": n,
            "deployment": {**cfg["deployment"], "first_expert": chip * n}}


def _program(cfg: dict, seed: int = 7):
    """The model and the served (bf16-drawn) weights, in float32."""
    from repro.models.model import Model
    model = Model(ds.program_config(cfg, "tiny"))
    w = jax.jit(lambda k: ds.init_weights(cfg, k))(seed_key(seed))
    params = ds.to_program(jax.tree.map(lambda a: a.astype(jnp.float32), w),
                           cfg)
    check_program_tree(params, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.float32)))
    return model, params


def _tokens(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], n)


def test_prefill_then_decode_through_the_latent_cache_match_the_reference():
    """Prefill of 30 tokens, then decode through the latent cache, each
    position's logits against the reference's full forward pass."""
    model, params = _program(TINY)
    toks = _tokens(40)
    want = np.asarray(ds.Reference(TINY, ds.reference_weights(TINY, 7))
                      .logits(toks))
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(1, 64)
        lg, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:30])
                                           [None]}, cache, logits_at=29)
        got = [np.asarray(lg[0])]
        for t in range(30, 39):
            lg, cache = model.decode_step(params, jnp.asarray(toks[t:t + 1])
                                          [None], cache,
                                          jnp.array([t], jnp.int32))
            got.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.stack(got), want[29:39], atol=2e-4,
                               rtol=2e-4)


def test_paged_serving_decodes_the_references_tokens():
    """The engine's paged path (latent pages in the layer scan's carry,
    the held experts read whole from the stack): greedy tokens that the
    reference ranks first, to float32 rounding, and expert counts that
    add up."""
    from repro.serving import EngineConfig, Request
    from repro.serving.engine import ServingEngine
    model, params = _program(TINY)
    eng = ServingEngine(model, params, EngineConfig(
        n_slots=3, max_len=64, cache="paged", block_size=16,
        dtype=jnp.float32))
    prompts = [_tokens(n, seed=n) for n in (5, 17, 33)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.astype(np.int32),
                           max_new_tokens=12))
    with jax.default_matmul_precision("highest"):
        done = {c.rid: c.tokens for c in eng.run()}
    ref = ds.Reference(TINY, ds.reference_weights(TINY, 7))
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, done[i][:-1]])
        lg = np.asarray(ref.logits(seq))[len(p) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(done[i])), done[i]]
        assert gap.max() < 1e-4, (i, gap)
    decoded = eng.tokens_generated - len(prompts)
    n = ds.dims(TINY)
    most = decoded * (n["L"] - n["n_dense"]) * n["K"]
    assert 0 < eng.moe_held_assignments < most
    assert eng.moe_dropped_assignments == 0


def test_a_full_share_counts_every_assignment():
    """Every routed expert held: each decoded token's every assignment
    in every MoE layer is counted as held, and none dropped."""
    from repro.serving import EngineConfig, Request
    from repro.serving.engine import ServingEngine
    cfg = _share(TINY, 0, TINY["deployment"]["routed_experts"])
    model, params = _program(cfg)
    eng = ServingEngine(model, params, EngineConfig(
        n_slots=2, max_len=64, cache="paged", block_size=16,
        dtype=jnp.float32))
    for i in range(2):
        eng.submit(Request(rid=i, prompt=_tokens(9 + i, i).astype(np.int32),
                           max_new_tokens=7))
    eng.run()
    n = ds.dims(cfg)
    decoded = eng.tokens_generated - 2
    assert eng.moe_held_assignments == (
        decoded * (n["L"] - n["n_dense"]) * n["K"])
    assert eng.moe_dropped_assignments == 0


def test_without_the_rope_permutation_the_logits_miss(monkeypatch):
    """The published layout interleaves each rope pair: served without
    ``to_program``'s permutation, the program computes another model."""
    toks = _tokens(24)
    want = np.asarray(ds.Reference(TINY, ds.reference_weights(TINY, 7))
                      .logits(toks))

    def last_logits():
        model, params = _program(TINY)
        with jax.default_matmul_precision("highest"):
            lg, _ = model.prefill(params, {"tokens": jnp.asarray(toks)[None]},
                                  model.init_cache(1, 32), logits_at=23)
        return np.abs(np.asarray(lg[0]) - want[23]).max()
    assert last_logits() < 2e-4
    monkeypatch.setattr(ds, "rope_order", lambda dr: np.arange(dr))
    assert last_logits() > 1e-2


def test_a_layer_and_an_expert_draw_alone():
    """The served tree's layer i, and expert e within it, are what the
    reference draws for them alone, whatever share holds the expert."""
    w = jax.jit(lambda k: ds.init_weights(TINY, k))(seed_key(2**31 + 5))
    n = ds.dims(TINY)
    _, k_layers = ds._keys(seed_key(2**31 + 5))
    alone = ds._draw_layer(n, k_layers, 2, True, jnp.bfloat16)
    for a, b in zip(jax.tree.leaves(jax.tree.map(lambda a: a[1], w["moe"])),
                    jax.tree.leaves(alone)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    other = _share(TINY, 0, 4)           # experts 0-3: 0-1 are TINY's
    w4 = jax.jit(lambda k: ds.init_weights(other, k))(seed_key(2**31 + 5))
    np.testing.assert_array_equal(
        np.asarray(w4["moe"]["experts"]["w_up"][:, :2], np.float32),
        np.asarray(w["moe"]["experts"]["w_up"], np.float32))


def test_the_held_shares_of_all_chips_sum_to_the_whole_layer():
    """One MoE layer, in the reference and in the program: the eight
    chips' shares, with the shared experts (computed alike on every
    chip) counted once, add up to the uncut layer."""
    from repro.models import moe as moe_lib
    n = ds.dims(TINY)
    chips = TINY["deployment"]["expert_parallel"]
    assert chips * n["n"] == n["E"]
    h = jax.random.normal(jax.random.PRNGKey(3), (20, n["d"]))
    whole_cfg = _share(TINY, 0, n["E"])
    key = seed_key(11)
    _, k_layers = ds._keys(key)

    def ref_layer(cfg):
        m = ds.dims(cfg)
        w = jax.tree.map(lambda a: a.astype(jnp.float32),
                         ds._draw_layer(m, k_layers, 1, True, jnp.bfloat16))
        no_shared = {**w, "shared": jax.tree.map(jnp.zeros_like,
                                                 w["shared"])}
        with jax.default_matmul_precision("highest"):
            return (np.asarray(ds._moe(cfg, m, w, h, False)),
                    np.asarray(ds._moe(cfg, m, no_shared, h, False)))

    def program_layer(cfg):
        w = jax.jit(lambda k: ds.init_weights(cfg, k))(key)
        p = ds.to_program(jax.tree.map(lambda a: a.astype(jnp.float32), w),
                          cfg)["stack"]["moe"]
        p = jax.tree.map(lambda a: a[0], p)
        from repro.models.model import Model
        model = Model(ds.program_config(cfg, "share"))
        with jax.default_matmul_precision("highest"):
            out, _ = moe_lib.moe_serve(p, model.cfg, h[None])
            routed, _ = moe_lib.moe_serve(
                {**p, "shared": jax.tree.map(jnp.zeros_like, p["shared"])},
                model.cfg, h[None])
        return np.asarray(out[0]), np.asarray(routed[0])

    for layer in (ref_layer, program_layer):
        whole, _ = layer(whole_cfg)
        parts = [layer(_share(TINY, c)) for c in range(chips)]
        shared = parts[0][0] - parts[0][1]
        total = shared + sum(routed for _, routed in parts)
        np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-5)
        assert np.abs(parts[0][1]).max() > 0


def test_a_run_is_correct():
    """The harness's whole run on the tiny configuration, through the
    model file: served, checked against the reference, correct."""
    bench = spec.load_benchmark()
    cell = spec.Cell(CELL, 1, "tiny-deepseek", TINY,
                     traffic.load_mix("tiny-chat", DATA),
                     [m for m in bench["end_to_end"]
                      if spec._applies(m, CELL)],
                     [m for m in bench["per_layer"]
                      if spec._applies(m, CELL)])
    assert cell.model is ds
    res, _, _ = run.run_cell(cell, 3_000_000_017, 2.0, False,
                             need_chip=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 10
    assert {"ttft_p95_s", "tpot_p95_ms", "setup_s"} <= set(res["metrics"])


def test_the_committed_configuration_is_the_catalogs_but_for_the_share():
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "deepseek-v2-lite")
    assert entry["reduced"] == ["n_routed_experts"]
    assert cfg["n_routed_experts"] == 8
    assert cfg["deployment"]["routed_experts"] == 64
    n = ds.dims(cfg)
    assert (n["L"], n["d"], n["H"], n["r"], n["mff"], n["K"]) == (
        27, 2048, 16, 512, 1408, 6)
    pc = ds.program_config(cfg, "d")
    assert (pc.n_experts, pc.experts_held, pc.first_expert) == (64, 8, 0)
    assert pc.norm_topk_prob is False and pc.rope_scaling.factor == 40.0
    s = cfg["serving"]
    assert s["pool_bytes"] == (s["max_blocks"] + 1) * s["block_size"] * (
        n["L"] * (n["r"] + n["dr"]) * 2)


def _trace(ops: list[tuple[str, float]]) -> Reduced:
    plane = "/device:TPU:0"
    events, t = [], 0
    for name, ms in ops:
        events.append(Event(plane, "XLA Ops", f"%{name} = f32[] op()", t,
                            int(ms * 1e6)))
        t += int(ms * 1e6)
    return Reduced(0, t, {plane: events}, [])


def test_the_two_rooflines_count_the_families_work():
    """Each reader's share is the work model's least time over the
    kernel's traced time: the latent bytes of the decoded tokens' live
    keys, and the held assignments' operations or one layer's held slab
    per call, whichever takes longer."""
    cfg = spec.load_cell(CELL).config
    work = ds.work(cfg)
    peaks = spec.peaks("TPU v5 lite")
    from bench import drive
    rec = drive.Record(0, 0.0, 100, 3, token_s=[1.0, 2.0, 3.0])
    log = drive.Log(records={0: rec}, window_s=5.0, trace_span=(0.0, 5.0))
    trace = _trace([("mla_decode_ctx.1", 0.5), ("moe_expert_gmm.3", 0.2),
                    ("moe_expert_gmm.3", 0.2), ("fusion.2", 1.0)])
    ctx = readers.Context(log=log, trace=trace, work=work, peaks=peaks,
                          chips=1, kv_bytes=2, act_bytes=2)
    f1, b1 = work.decode_attention_work(101, 2)
    f2, b2 = work.decode_attention_work(102, 2)
    assert b1 == 27 * (576 * 101 + 16 * (2 * 512 + 64)) * 2
    least = max((f1 + f2) / peaks["bf16_flops"],
                (b1 + b2) / peaks["hbm_bytes_per_s"])
    mla = spec.metric_reader("mla_decode_roofline")(ctx)
    assert mla == pytest.approx(100 * least / 0.5e-3)
    flops = (100 + 2) * work.expert_token_flops()
    assert work.expert_token_flops() == 26 * 6 * 8 / 64 * 6 * 2048 * 1408
    data = 2 * 8 * 3 * 2048 * 1408 * 2
    least = max(flops / peaks["bf16_flops"],
                data / peaks["hbm_bytes_per_s"])
    moe = spec.metric_reader("moe_expert_roofline")(ctx)
    assert moe == pytest.approx(100 * least / 0.4e-3)
    # a dense family has no held experts, a trace without the kernel
    # nothing to read
    dense = dataclasses.replace(ctx, work=spec.load_cell(
        "qwen3-0.6b.chat").model.work(spec.load_cell(
            "qwen3-0.6b.chat").config))
    assert spec.metric_reader("moe_expert_roofline")(dense) is None
    bare = dataclasses.replace(ctx, trace=_trace([("fusion.2", 1.0)]))
    assert spec.metric_reader("moe_expert_roofline")(bare) is None
    assert spec.metric_reader("mla_decode_roofline")(bare) is None
