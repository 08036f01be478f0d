"""The model-file contract: every family's file exports it, only family
files reach ``dense.py``, and binding the dense family behind the
contract changed no weight, logit or per-layer reading."""
from __future__ import annotations

import ast
import hashlib
import importlib
import inspect
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import readers, spec
from bench import trace as tl
from bench.drive import Log, Record
from bench.models import dense, qwen3
from bench.models.common import seed_key

DATA = pathlib.Path(__file__).resolve().parent / "data"
MODELS = spec.ROOT / "bench" / "models"
# shared code in bench/models/ that names no family
LIBRARIES = {"common", "dense"}
FAMILIES = sorted(p.stem for p in MODELS.glob("*.py")
                  if p.stem not in LIBRARIES)
# each contract name and the leading parameters it takes
CONTRACT = {"program_config": ["cfg", "name"],
            "init_weights": ["cfg", "key", "dtype"],
            "to_program": ["w", "cfg"],
            "reference_weights": ["cfg", "seed"],
            "Reference": ["cfg", "weights", "fp8"],
            "work": ["cfg"]}
WORK = ("token_flops", "prefill_flops", "decode_attention_work",
        "prefill_attention_work")


def _tiny() -> dict:
    return json.loads((DATA / "tiny-qwen3.json").read_text())


@pytest.mark.parametrize("family", FAMILIES)
def test_a_model_file_exports_the_contract(family):
    mod = importlib.import_module(f"bench.models.{family}")
    for name, params in CONTRACT.items():
        fn = getattr(mod, name)
        got = list(inspect.signature(fn).parameters)
        assert got[:len(params)] == params, (name, got)
    model = mod.work(_tiny())
    for name in WORK:
        assert callable(getattr(model, name)), name


def test_every_configuration_names_a_family_file():
    for c in spec.load_benchmark()["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["model_type"] in FAMILIES, c["name"]
    assert _tiny()["model_type"] in FAMILIES


def _imports(path: pathlib.Path) -> set[str]:
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
        elif isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
    return names


def test_only_family_files_reach_dense():
    """The harness and the shared helpers reach the dense equations only
    through a family's model file."""
    reach = sorted(str(p.relative_to(spec.ROOT))
                   for p in (spec.ROOT / "bench").rglob("*.py")
                   if "bench.models.dense" in _imports(p))
    assert reach and all(pathlib.Path(p).parent == pathlib.Path(
        "bench/models") and pathlib.Path(p).stem in FAMILIES
        for p in reach), reach


# sha256 over (path, bytes) of every leaf of tiny-qwen3's bf16 tree, as
# the harness drew it before the contract (dense.init_weights with
# qwen3.FLAGS, jax 0.9.0, on the CPU)
TREE_SHA256 = {
    5: "028b59e6260647345199ea6a40b3ab798952d52daf8ccd58711a31d9ba16d266",
    2**31 + 5:
        "8cd2eeedc4d35bc087aec31afc4bc72c0c93c41173d74e18c5de910d17885223",
}


@pytest.mark.parametrize("seed", sorted(TREE_SHA256))
def test_qwen3_draws_the_tree_the_dense_family_drew(seed):
    cfg = _tiny()
    key = seed_key(seed)
    got = jax.jit(lambda k: qwen3.init_weights(cfg, k, jnp.bfloat16))(key)
    old = jax.jit(lambda k: dense.init_weights(
        cfg, qwen3.FLAGS, k, jnp.bfloat16))(key)
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert [p for p, _ in flat] == [
        p for p, _ in jax.tree_util.tree_flatten_with_path(old)[0]]
    h = hashlib.sha256()
    for (path, a), b in zip(flat, jax.tree.leaves(old)):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == TREE_SHA256[seed]
    prog = qwen3.to_program(got, cfg)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(
            dense.to_program(old, cfg, qwen3.FLAGS))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ref = qwen3.reference_weights(cfg, seed)
    tokens = np.random.default_rng(seed % 97).integers(
        0, cfg["vocab_size"], 24)
    for fp8 in (False, True):
        a = qwen3.Reference(cfg, ref, fp8=fp8).logits(tokens)
        b = dense.Reference(cfg, qwen3.FLAGS, dense.reference_weights(
            cfg, qwen3.FLAGS, seed), fp8).logits(tokens)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


PROMPTS = (32, 100, 257, 512, 700, 1024, 1536, 64, 333, 2000)


def _log() -> Log:
    """A fixed request log over the traced interval: ten requests of
    mixed prompt lengths, some prefilled inside it, each decoding a
    token every 0.11 s."""
    recs = {}
    for i, p in enumerate(PROMPTS):
        n = 6 + 5 * i
        ts = [0.4 + 0.37 * i + 0.11 * j for j in range(n)]
        recs[i] = Record(i, 0.0, p, n, token_s=ts, tokens=list(range(n)))
    log = Log(records=recs, window_s=10.0)
    log.trace_span = (1.0, 5.0)
    return log


# the parent's readings (readers.Context(dims=dense.dims(cfg))) of the
# committed qwen3-0.6b configuration, on each recorded trace with _log()
PARENT = {
    "trace_qwen3-0.6b.chat": {"mfu": 15.202862815703051,
                              "paged_decode_roofline": 9.863166789535983,
                              "flash_prefill_roofline": None},
    "spans_qwen3-0.6b.chat": {"mfu": 9.383745559698685,
                              "paged_decode_roofline": 6.900397653401169,
                              "flash_prefill_roofline": 54.95358893382786},
}


@pytest.mark.parametrize("trace", sorted(PARENT))
def test_readers_read_what_they_read_before_the_contract(trace):
    cell = spec.load_cell("qwen3-0.6b.chat")
    rec = json.loads((DATA / f"{trace}.json").read_text())
    ctx = readers.Context(
        log=_log(), trace=tl.reduce([tl.Event(*r) for r in rec["events"]]),
        work=cell.model.work(cell.config), peaks=spec.peaks("TPU v5 lite"),
        chips=1, kv_bytes=2, act_bytes=2)
    for name, want in PARENT[trace].items():
        assert spec.metric_reader(name)(ctx) == want, name
