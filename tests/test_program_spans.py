"""The serving program's profiler spans, read back from a real trace of a
Router serving a tiny paged engine: every span name is there, they nest
Router poll > engine step > admission > prefill, and they come as many
times as the work they wrap."""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace as tl
from repro.configs.base import ArchConfig
from repro.models.model import Model
from repro.serving import EngineConfig, Request, Router
from repro.serving.backend import ThreadBackend

TINY = pathlib.Path(__file__).resolve().parent / "bench" / "data" / (
    "tiny-qwen3.json")
NAMES = ("router.poll", "engine.step", "engine.admit", "cache.flush",
         "engine.prefill", "engine.decode", "engine.readback",
         "engine.emit")


@pytest.fixture(scope="module")
def tiny():
    """The tiny qwen3 widths of the benchmark's test configuration, with
    its paged serving settings (one request per prefill)."""
    c = json.loads(TINY.read_text())
    s = c["serving"]
    cfg = ArchConfig(
        name="tiny-qwen3", arch_type="dense", source=c["source"],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=True, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=True,
        act="silu", mlp_gated=True, pos_embed="rope")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    config = EngineConfig(cache="paged", block_size=s["block_size"],
                          max_len=s["max_len"], max_blocks=s["max_blocks"],
                          max_seqs=s["max_seqs"],
                          prefix_cache=s["prefix_cache"],
                          batch_admit=s["batch_admit"], chunk_tokens=4)
    return model, params, config


def _requests(vocab, max_new, base=0):
    rng = np.random.default_rng(base)
    return [Request(rid=base + i, prompt=rng.integers(
        0, vocab, (20 + 3 * i,), dtype=np.int32), max_new_tokens=m)
        for i, m in enumerate(max_new)]


def _serve(router, reqs):
    handles = [router.submit(r) for r in reqs]
    for h in handles:
        h.result()


def _traced(tmp_path, model, params, config, containers, max_new):
    """Serve once untraced (so nothing compiles in the trace), then again
    under the profiler: the trace's program spans, and the decode chunks
    the engines ran in it."""
    backend = ThreadBackend(model, params, containers, config=config)
    vocab = model.cfg.vocab_size
    with Router(backend) as router:
        _serve(router, _requests(vocab, max_new, base=100))
        before = sum(e.chunks for e in backend.engines)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(router, _requests(vocab, max_new))
        finally:
            jax.profiler.stop_trace()
        chunks = sum(e.chunks for e in backend.engines) - before
    path = next(tmp_path.rglob("*.xplane.pb"))
    return [e for e in tl.load(str(path)) if e.name in NAMES], chunks


def _by_name(events, name):
    return [e for e in events if e.name == name]


def _covers(outer, inner):
    return (outer.start_ns <= inner.start_ns
            and inner.end_ns <= outer.end_ns)


def _inside(inner, outers):
    return any(_covers(o, inner) for o in outers)


def test_one_container_spans_nest_and_count(tiny, tmp_path):
    model, params, config = tiny
    max_new = (3, 9, 15)
    ev, chunks = _traced(tmp_path, model, params, config, 1, max_new)
    assert {e.name for e in ev} == set(NAMES)
    polls, steps, admits = (_by_name(ev, n) for n in
                            ("router.poll", "engine.step", "engine.admit"))
    prefills = _by_name(ev, "engine.prefill")
    # batch_admit off: one prefill per request
    assert len(prefills) == len(max_new)
    for p in prefills:
        admit = next(a for a in admits if _covers(a, p))
        step = next(s for s in steps if _covers(s, admit))
        assert _inside(step, polls)
    # one decode, one readback and one emit per step that decoded, each
    # readback inside its own step
    reads = _by_name(ev, "engine.readback")
    assert len(reads) == len(_by_name(ev, "engine.decode")) == len(
        _by_name(ev, "engine.emit")) == chunks > 0
    for r in reads:
        step = next(s for s in steps if _covers(s, r))
        assert sum(_covers(step, x) for x in reads) == 1
    # finished rows are scrubbed at a later step's admission
    for f in _by_name(ev, "cache.flush"):
        assert _inside(f, admits)


def test_worker_thread_steps_lie_in_the_main_threads_poll(tiny, tmp_path):
    model, params, config = tiny
    ev, _ = _traced(tmp_path, model, params, config, 2, (6, 6, 6, 6))
    polls, steps = _by_name(ev, "router.poll"), _by_name(ev, "engine.step")
    assert steps and all(_inside(s, polls) for s in steps)
    # two containers stepped at once, so on two worker threads
    assert any(a.start_ns < b.start_ns < a.end_ns
               for a in steps for b in steps if a is not b)
