"""A whole run of the harness at test widths on the CPU, past its look
for a chip: it is correct as served, and not correct with the timed path
broken underneath or with the fp8 control in the program's place. A
family that no file of ``bench/`` names runs through the model-file
contract alone."""
from __future__ import annotations

import json
import pathlib
import sys
import types

import jax.numpy as jnp
import pytest

from bench import correct, drive, run, spec, traffic
from bench.models import dense

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "qwen3-0.6b.chat"
# the committed configuration's widths and vocabulary
WIDE = ("hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "vocab_size")


def _cell() -> spec.Cell:
    bench = spec.load_benchmark()
    cfg = json.loads((DATA / "tiny-qwen3.json").read_text())
    mix = traffic.load_mix("tiny-chat", DATA)
    return spec.Cell(
        CELL, 1, "tiny-qwen3", cfg, mix,
        [m for m in bench["end_to_end"] if spec._applies(m, CELL)],
        [m for m in bench["per_layer"] if spec._applies(m, CELL)])


@pytest.fixture(scope="module")
def cell():
    return _cell()


def test_a_run_is_correct_and_reports_its_metrics(cell):
    res, _, _ = run.run_cell(cell, 3_000_000_011, 2.0, False,
                            need_chip=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 10
    names = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == names
    assert res["checks"]["logit_gap"]["value"] <= (
        res["checks"]["logit_gap"]["limit"])


def test_a_closed_loop_run_keeps_its_clients_busy(cell):
    """The closed loop a backlog cell would use: every finished request
    is followed by the next one, and tokens/s is over the whole window."""
    import dataclasses
    mix = dataclasses.replace(cell.mix, loop="closed", clients_per_row=1.25)
    closed = dataclasses.replace(
        cell, mix=mix,
        end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}])
    res, _, _ = run.run_cell(closed, 5, 2.0, False, need_chip=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["attempted"] > 10


def test_a_token_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    from repro.models.model import Model
    orig = Model.decode_chunk
    vocab = cell.config["vocab_size"]

    def altered(self, params, cache, state, n_tokens, **kw):
        toks, emitted, new_state, cache = orig(self, params, cache, state,
                                               n_tokens, **kw)
        # every third position decodes a token one id past the model's
        bad = (new_state["pos"] % 3 == 0)[:, None]
        toks = jnp.where(bad, (toks + 1) % vocab, toks)
        new_state = {**new_state, "tokens": toks[:, -1]}
        return toks, emitted, new_state, cache

    monkeypatch.setattr(Model, "decode_chunk", altered)
    res, _, _ = run.run_cell(cell, 12, 2.0, False, need_chip=False)
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_fp8_control_is_not_correct(cell, monkeypatch):
    """The control, the reference computed in fp8, put in the program's
    place inside the run's own comparison: at each position of the served
    requests, the token the control ranks first is judged instead of the
    served one. At qwen3-0.6b's widths and vocabulary (two layers, longer
    outputs) and against the committed configuration's limit, the run is
    not correct, while the served tokens are within the limit."""
    import dataclasses
    committed = spec.load_cell(CELL).config
    cfg = {**cell.config, **{k: committed[k] for k in WIDE},
           "num_hidden_layers": 2, "limits": committed["limits"]}
    wide = dataclasses.replace(cell, config=cfg,
                               mix=traffic.load_mix("wide-chat", DATA))
    orig = correct.logit_gaps
    served = []

    def control_in_place(log, recs, ref, pad_to, control=None):
        served.append(max(orig(log, recs, ref, pad_to)))
        fp8 = wide.model.Reference(ref.cfg, ref.w, fp8=True)
        return orig(log, recs, ref, pad_to, control=fp8)

    monkeypatch.setattr(correct, "logit_gaps", control_in_place)
    limit = committed["limits"]["logit_gap"]
    for seed in (21, 22, 23):
        res, _, _ = run.run_cell(wide, seed, 3.0, False, need_chip=False)
        gap = res["checks"]["logit_gap"]
        assert gap["limit"] == limit
        assert not res["correct"] and gap["value"] > limit, gap
    assert len(served) == 3 and max(served) <= limit, served


CONTRACT = ("program_config", "init_weights", "to_program",
            "reference_weights", "Reference", "work")


def test_a_new_family_runs_through_its_model_file_alone(cell, monkeypatch):
    """A test-only family, registered as ``bench.models.spy_family`` and
    named by the configuration's ``model_type``: the dense equations with
    qwen3's flags behind the contract, every call recorded. The harness
    reaches the model only through it, and calls into ``dense`` come from
    the family alone."""
    import dataclasses
    from bench.models import qwen3
    calls, callers = [], set()

    def recorded(name, fn):
        def call(*args, **kw):
            calls.append(name)
            return fn(*args, **kw)
        return call

    for name in CONTRACT + ("dims",):
        fn = getattr(dense, name)

        def spy(*args, _fn=fn, **kw):
            callers.add(sys._getframe(1).f_globals["__name__"])
            return _fn(*args, **kw)
        monkeypatch.setattr(dense, name, spy)

    flags = qwen3.FLAGS
    family = types.ModuleType("bench.models.spy_family")
    family.program_config = recorded(
        "program_config",
        lambda cfg, name: dense.program_config(cfg, flags, name))
    family.init_weights = recorded(
        "init_weights",
        lambda cfg, key, dtype=jnp.bfloat16: dense.init_weights(
            cfg, flags, key, dtype))
    family.to_program = recorded(
        "to_program", lambda w, cfg: dense.to_program(w, cfg, flags))
    family.reference_weights = recorded(
        "reference_weights",
        lambda cfg, seed: dense.reference_weights(cfg, flags, seed))
    family.Reference = recorded(
        "Reference",
        lambda cfg, w, fp8=False: dense.Reference(cfg, flags, w, fp8))
    family.work = recorded("work", lambda cfg: dense.work(cfg))
    monkeypatch.setitem(sys.modules, family.__name__, family)
    spied = dataclasses.replace(
        cell, config={**cell.config, "model_type": "spy_family"})
    assert spied.model is family

    res, _, _ = run.run_cell(spied, 2**31 + 17, 2.0, False,
                             need_chip=False)
    assert res["correct"], res["checks"]
    assert set(calls) == set(CONTRACT), calls
    assert callers == {__name__, dense.__name__}, callers


def test_the_engine_gets_every_serving_key_it_has():
    """The committed configuration builds the engine it always built, and
    engine keys such as ``chunked`` reach the engine."""
    from repro.serving import EngineConfig
    cfg = spec.load_cell(CELL).config
    s = cfg["serving"]
    assert run._engine_config(cfg) == EngineConfig(
        dtype=jnp.bfloat16, greedy=True, cache="paged",
        block_size=s["block_size"], max_len=s["max_len"],
        max_blocks=s["max_blocks"], max_seqs=s["max_seqs"],
        prefix_cache=s["prefix_cache"], batch_admit=s["batch_admit"])
    more = {**cfg, "serving": {**s, "chunked": False, "chunk_tokens": 4}}
    got = run._engine_config(more)
    assert got.chunked is False and got.chunk_tokens == 4


@pytest.mark.parametrize("key", ["blok_size", "greedy", "dtype"])
def test_an_unknown_serving_key_is_refused(key):
    """A misspelt key, or an engine field the harness sets itself, fails
    at set-up instead of leaving the engine at a silent default."""
    cfg = spec.load_cell(CELL).config
    bad = {**cfg, "serving": {**cfg["serving"], key: 16}}
    with pytest.raises(ValueError, match=key):
        run._engine_config(bad)


def test_the_sample_holds_the_longest_request():
    recs = {i: drive.Record(i, 0.0, 10, 40, tokens=[1] * (10 + i),
                            token_s=[1.0] * (10 + i), done_s=2.0)
            for i in range(30)}
    recs[99] = drive.Record(99, 0.0, 10, 40, tokens=[1] * 200,
                            token_s=[1.0] * 200, done_s=None)
    log = drive.Log(records=recs, window_s=5.0)
    s = correct.sample(log, 4)
    assert s[0].rid == 29
    assert 99 not in {r.rid for r in s}
    assert sum(len(r.tokens) for r in s) >= correct.SAMPLE_TOKENS or (
        len(s) == correct.SAMPLE_MAX)
    assert [r.rid for r in s] == [r.rid for r in correct.sample(log, 4)]


def test_no_accelerator_means_no_result():
    """From the command line, on the CPU: a non-zero exit and no line."""
    import os
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELL, "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
