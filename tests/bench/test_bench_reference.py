"""The plain float32 reference against the program's own full forward
pass, at test widths on the CPU, through the model-file contract: for
each model file, and for the dense family's LayerNorm, partial-rotary,
untied-head path."""
from __future__ import annotations

import importlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.models import dense
from bench.models.common import check_program_tree, seed_key

SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "intermediate_size": 96, "num_hidden_layers": 2,
         "vocab_size": 300}


def dense_family(flags: dict) -> types.SimpleNamespace:
    """A dense model file's contract for ``flags``, as ``qwen3.py`` binds
    its own."""
    return types.SimpleNamespace(
        program_config=lambda cfg, name: dense.program_config(
            cfg, flags, name),
        init_weights=lambda cfg, key, dtype=jnp.bfloat16: dense.init_weights(
            cfg, flags, key, dtype),
        to_program=lambda w, cfg: dense.to_program(w, cfg, flags),
        reference_weights=lambda cfg, seed: dense.reference_weights(
            cfg, flags, seed),
        Reference=lambda cfg, w, fp8=False: dense.Reference(
            cfg, flags, w, fp8),
        work=dense.work)


def _small(config_file: str, **extra) -> tuple[dict, object]:
    cfg = json.loads((spec.ROOT / "bench" / "configs" /
                      config_file).read_text())
    cfg.update(SMALL, **extra)
    return cfg, importlib.import_module(f"bench.models.{cfg['model_type']}")


CASES = {
    "qwen3-0.6b": ("qwen3-0.6b.json", None,
                   dict(num_key_value_heads=2, head_dim=16)),
    # LayerNorm with bias, rotary on a quarter of each head, untied head
    # and no q/k norm, as StableLM 2 has them
    "layernorm-partial-rotary": (
        "qwen3-0.6b.json", {"norm": "layernorm", "qk_norm": False},
        dict(num_key_value_heads=4, head_dim=None, layer_norm_eps=1e-5,
             partial_rotary_factor=0.25, tie_word_embeddings=False)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_matches_the_programs_forward(name):
    from repro.models.model import Model
    file, flags, extra = CASES[name]
    cfg, own = _small(file, **extra)
    mod = dense_family(flags) if flags else own
    model = Model(mod.program_config(cfg, name))
    key = seed_key(7)
    w = jax.jit(lambda k: mod.init_weights(cfg, k, jnp.float32))(key)
    params = mod.to_program(w, cfg)
    check_program_tree(params, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.float32)))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
    want = mod.Reference(cfg, w).logits(tokens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_reference_weights_are_the_served_values():
    cfg, mod = _small("qwen3-0.6b.json", **CASES["qwen3-0.6b"][2])
    served = jax.jit(lambda k: mod.init_weights(cfg, k))(
        seed_key(2**31 + 5))
    ref = mod.reference_weights(cfg, 2**31 + 5)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(ref)):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))
    other = mod.reference_weights(cfg, 5)
    assert not np.array_equal(np.asarray(ref["embed"]),
                              np.asarray(other["embed"]))


def test_a_layout_the_program_does_not_have_is_refused():
    cfg, mod = _small("qwen3-0.6b.json", **CASES["qwen3-0.6b"][2])
    from repro.models.model import Model
    model = Model(mod.program_config(cfg, "q"))
    w = jax.jit(lambda k: mod.init_weights(cfg, k))(seed_key(1))
    params = mod.to_program(w, cfg)
    del params["stack"]["attn"]["q_norm"]
    with pytest.raises(ValueError, match="missing"):
        check_program_tree(params, jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0))))
