"""Sharding-rule unit tests on an AbstractMesh (no devices needed)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_config
from repro.launch.sharding import ShardingRules
from repro.models.model import Model

MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(tree):
    return jax.tree.map(lambda ns: ns.spec, tree,
                        is_leaf=lambda x: hasattr(x, "spec"))


@pytest.fixture(scope="module")
def qwen_params_struct():
    model = Model(get_config("qwen3-8b"))
    return jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                          jax.random.PRNGKey(0))


def test_attention_param_specs(qwen_params_struct):
    rules = ShardingRules(MESH, train=True)
    specs = _specs(rules.params(qwen_params_struct))
    stack = specs["stack"]
    # wq (L, d, H, hd): heads on model, d on data (FSDP); hd NEVER sharded
    assert stack["attn"]["wq"] == P(None, "data", "model")
    # kv heads = 8 < model=16: replicated on model
    assert stack["attn"]["wk"] == P(None, "data")
    assert stack["attn"]["wo"] == P(None, "model", None, "data")
    assert stack["mlp"]["w_up"] == P(None, "data", "model")
    assert stack["mlp"]["w_down"] == P(None, "model", "data")
    # embed (V, d): vocab on model
    assert specs["embed"]["table"] == P("model", "data")
    assert specs["lm_head"]["w"] == P("data", "model")


def test_inference_replicates_over_data(qwen_params_struct):
    rules = ShardingRules(MESH, train=False, fsdp=False)
    specs = _specs(rules.params(qwen_params_struct))
    stack = specs["stack"]
    assert stack["attn"]["wq"] == P(None, None, "model")
    assert stack["mlp"]["w_down"] == P(None, "model")
    flat = jax.tree.leaves(
        jax.tree.map(lambda s: "data" in jax.tree.leaves(tuple(s)) if s else False,
                     stack, is_leaf=lambda x: isinstance(x, P)))
    assert not any(flat), "inference (no fsdp) must not shard over data"


def test_expert_parallel_when_divisible():
    model = Model(get_config("deepseek-v2-lite-16b"))
    struct = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    rules = ShardingRules(MESH, train=True)
    specs = _specs(rules.params(struct))
    up = specs["stack"]["moe"]["experts"]["w_up"]
    # (L, E=64, d, ff): E divides 16 → expert-parallel
    assert up == P(None, "model", "data")


def test_tensor_parallel_experts_when_not_divisible():
    model = Model(get_config("mixtral-8x22b"))
    struct = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    rules = ShardingRules(MESH, train=True)
    specs = _specs(rules.params(struct))
    up = specs["stack"]["moe"]["experts"]["w_up"]
    # (L, E=8, d, ff): E doesn't divide 16 → shard ff
    assert up == P(None, None, "data", "model")


def test_cache_specs_gqa_decode():
    model = Model(get_config("qwen3-8b"))
    cache = jax.eval_shape(lambda: model.init_cache(128, 32768,
                                                    dtype=jnp.bfloat16))
    rules = ShardingRules(MESH, train=False)
    specs = _specs(rules.cache(cache, batch=128))
    kspec = specs["stack"]["k"]
    # (L, B, W, kv=8, hd): kv doesn't divide → sequence-parallel decode
    assert kspec == P(None, "data", "model")


def test_cache_specs_long_context_idle_batch():
    model = Model(get_config("gemma3-27b"))
    cache = jax.eval_shape(lambda: model.init_cache(1, 524_288,
                                                    dtype=jnp.bfloat16))
    rules = ShardingRules(MESH, train=False)
    specs = _specs(rules.cache(cache, batch=1))
    gspec = specs["super"]["global"]["k"]
    # (n_super, B=1, W, kv=16, hd): batch idle → seq over data, kv over model
    assert gspec == P(None, None, "data", "model")


def test_cache_specs_mla_latent():
    model = Model(get_config("deepseek-v2-lite-16b"))
    cache = jax.eval_shape(lambda: model.init_cache(128, 32768,
                                                    dtype=jnp.bfloat16))
    rules = ShardingRules(MESH, train=False)
    specs = _specs(rules.cache(cache, batch=128))
    ckv = specs["stack"]["ckv"]        # (L, B, S, r=512)
    # seq over model (distributed softmax) — NOT r (r-sharding makes GSPMD
    # all-gather the whole latent cache per layer)
    assert ckv == P(None, "data", "model")


def test_ssm_cache_heads_on_model():
    model = Model(get_config("mamba2-2.7b"))
    cache = jax.eval_shape(lambda: model.init_cache(128, 32768,
                                                    dtype=jnp.bfloat16))
    rules = ShardingRules(MESH, train=False)
    specs = _specs(rules.cache(cache, batch=128))
    state = specs["stack"]["state"]    # (L, B, nh=80, hd, ds)
    assert state == P(None, "data", "model")


def test_batch_spec_multipod():
    rules = ShardingRules(POD_MESH, train=True)
    specs = _specs(rules.batch({"tokens": jax.ShapeDtypeStruct((256, 4096),
                                                               jnp.int32)}))
    assert specs["tokens"] == P(("pod", "data"))


def test_batch_too_small_replicates():
    rules = ShardingRules(MESH, train=False)
    specs = _specs(rules.batch({"tokens": jax.ShapeDtypeStruct((1, 128),
                                                               jnp.int32)}))
    assert specs["tokens"] == P()


def test_opt_state_mirrors_params(qwen_params_struct):
    from repro.train.optimizer import init_opt_state
    opt = jax.eval_shape(init_opt_state, qwen_params_struct)
    rules = ShardingRules(MESH, train=True)
    specs = _specs(rules.opt_state(opt))
    assert specs["m"]["stack"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["step"] == P()


def test_head_dim_never_sharded(qwen_params_struct):
    """head_dim is always a contraction dim of the attention scores — a
    sharded head_dim forces an all-reduce per flash tile (the exact bug the
    role-based rules exist to prevent)."""
    for cfgname in ("qwen3-8b", "gemma3-27b", "whisper-large-v3"):
        model = Model(get_config(cfgname))
        struct = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                                jax.random.PRNGKey(0))
        rules = ShardingRules(MESH, train=True)
        specs = _specs(rules.params(struct))

        def check(path, spec, leaf):
            names = [str(getattr(p, "key", p)) for p in path]
            if names[-1] in ("wq", "wk", "wv"):
                rank = len(leaf.shape)
                full = tuple(spec) + (None,) * (rank - len(spec))
                assert full[-1] is None, (names, spec)   # hd dim unsharded

        jax.tree_util.tree_map_with_path(
            check, specs, struct, is_leaf=lambda x: isinstance(x, P))
