"""qwen3-0.6b's serving programs, the benchmarked dense path, lower to
the StableHLO they lowered to before the MoE and latent changes beside
them (the layer scan's carried pools, the held-expert dispatch, YaRN and
the attention scale): the decode chunk at the qwen3-0.6b.chat cell's
shapes (72 rows, 460 pages of 128 tokens, one token a chunk) and the
engine's prefill of one 512-token bucket, lowered on the CPU. The
digests are of the text as the installed JAX prints it."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.models.cache import PagedLayout
from repro.models.model import Model

DECODE = "364e9c991072321ad60f7acf5a76696d7ae1b6b7281b4f53b071f4d1042b6bd3"
PREFILL = "03ec01a946e85be0c3202468502debd03ec3573818b169cbbd25205ab9b53109"


def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


def test_qwen3_serving_programs_lower_as_before():
    model = Model(get_config("qwen3-0.6b"))
    bf16, i32 = jnp.bfloat16, jnp.int32
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), bf16))
    cache = jax.eval_shape(lambda: model.init_cache(
        72, 2048, bf16, layout=PagedLayout(128, 460)))
    rows = jax.ShapeDtypeStruct((72,), i32)
    state = {"tokens": rows, "pos": rows, "remaining": rows,
             "active": jax.ShapeDtypeStruct((72,), jnp.bool_),
             "key": jax.eval_shape(lambda: jax.random.PRNGKey(0))}

    def chunk(p, c, s):
        return model.decode_chunk(p, c, s, 1, max_len=2048)

    def prefill(p, b, li):
        return model.prefill(p, b, model.init_cache(1, 2048), logits_at=li)
    decode = jax.jit(chunk, donate_argnums=(1,)).lower(params, cache, state)
    fill = jax.jit(prefill).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, 512), i32)},
        jax.ShapeDtypeStruct((1,), i32))
    assert _digest(decode) == DECODE
    assert _digest(fill) == PREFILL
