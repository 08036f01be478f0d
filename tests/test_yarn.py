"""YaRN rope scaling as DeepSeek-V2 computes it, against values written
from Hugging Face's ``DeepseekV2YarnRotaryEmbedding``, ``yarn_get_mscale``
and ``DeepseekV2Attention.softmax_scale`` at DeepSeek-V2-Lite's settings
(rope dim 64, theta 10^4, factor 40 over 4096 positions, beta 32 / 1,
mscale = mscale_all_dim = 0.707)."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.models.attention import mla_softmax_scale
from repro.models.layers import (apply_rope, rope_freqs, yarn_correction_range,
                                 yarn_mscale)

CFG = get_config("deepseek-v2-lite-16b")
S = CFG.rope_scaling


def _hf_inv_freq(dim=64, base=10_000.0, factor=40.0, orig=4096,
                 beta_fast=32.0, beta_slow=1.0):
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``'s inv_freq."""
    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / base ** pos
    freq_inter = 1.0 / (factor * base ** pos)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask, (low, high)


def test_the_published_settings_are_read():
    assert (S.factor, S.original_max_position_embeddings, S.beta_fast,
            S.beta_slow, S.mscale, S.mscale_all_dim) == (
        40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert CFG.norm_topk_prob is False


def test_correction_range_and_inv_freq():
    want, (low, high) = _hf_inv_freq()
    assert yarn_correction_range(64, 10_000.0, S) == (low, high) == (10, 23)
    got = np.asarray(rope_freqs(64, 10_000.0, scaling=S))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(rope_freqs(64, 10_000.0))
    # pairs below the range keep the original frequency, pairs past it
    # are interpolated by 1/factor, and the ramp blends in between
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-7)
    np.testing.assert_allclose(got[23:], plain[23:] / 40.0, rtol=1e-6)
    assert np.all((got[11:23] < plain[11:23])
                  & (got[11:23] > plain[11:23] / 40.0))


def test_mscale_and_softmax_scale():
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert math.isclose(yarn_mscale(40.0, 0.707), m)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # cos/sin are scaled by mscale's factor over mscale_all_dim's: 1 here
    assert yarn_mscale(S.factor, S.mscale) / yarn_mscale(
        S.factor, S.mscale_all_dim) == 1.0
    assert math.isclose(mla_softmax_scale(CFG), 192 ** -0.5 * m * m,
                        rel_tol=1e-12)
    assert math.isclose(mla_softmax_scale(CFG), 0.1147213867929261,
                        rel_tol=1e-12)


def test_rope_rotates_by_the_blended_frequencies():
    """HF's rotate_half form (in the program's de-interleaved column
    order) at positions past the original context."""
    x = np.random.default_rng(0).standard_normal((1, 3, 2, 64)).astype(
        np.float32)
    pos = np.array([[5, 4100, 9000]])
    got = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos),
                                10_000.0, scaling=S))
    inv, _ = _hf_inv_freq()
    ang = pos[0][:, None].astype(np.float64) * inv
    emb = np.concatenate([ang, ang], -1)[:, None, :]
    rot = np.concatenate([-x[..., 32:], x[..., :32]], -1)
    want = x * np.cos(emb) + rot * np.sin(emb)
    np.testing.assert_allclose(got, want, atol=2e-3)
