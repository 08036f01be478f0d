"""Operations and bytes the served work needs, from the request log.

Work is counted from what was served: each prompt token prefilled and
each generated token at its position. It is never counted from a
kernel's grid or a padded shape, so a kernel that stops touching padding
reads a higher share of its roofline, never one above 100%.

Each function takes the dense family's sizes (``bench.models.dense.dims``)
and the cache's bytes per element. A family's model file hands the
readers its work model (``work(cfg)``, ``bench/spec.py``): ``DenseWork``
binds these formulas to one configuration's sizes; a family with other
attention or experts brings its own.
"""
from __future__ import annotations

import dataclasses


def matmul_params(n: dict) -> int:
    """Weights that multiply each token's activations: the projections,
    the MLP and the head (the embedding lookup is a gather)."""
    d, H, Hkv, hd, ff, V, L = (n[k] for k in
                               ("d", "H", "Hkv", "hd", "ff", "V", "L"))
    per_layer = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff
    return L * per_layer + d * V


def attention_flops(n: dict, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys,
    summed over layers."""
    return 4 * n["L"] * n["H"] * n["hd"] * keys


def token_flops(n: dict, keys: int) -> int:
    """Forward operations of one token that attends to ``keys`` keys."""
    return 2 * matmul_params(n) + attention_flops(n, keys)


def prefill_flops(n: dict, prompt_len: int) -> int:
    """A causal prefill of ``prompt_len`` tokens: token i sees i + 1 keys."""
    s = prompt_len
    return (2 * matmul_params(n) * s
            + 4 * n["L"] * n["H"] * n["hd"] * s * (s + 1) // 2)


def decode_attention_work(n: dict, keys: int, kv_bytes: int
                          ) -> tuple[int, int]:
    """Paged decode attention for one query over ``keys`` live keys:
    operations, and bytes read and written (the live K and V pages, the
    query and the output), summed over layers."""
    L, H, Hkv, hd = n["L"], n["H"], n["Hkv"], n["hd"]
    flops = 4 * L * H * hd * keys
    data = L * (2 * keys * Hkv * hd * kv_bytes + 2 * H * hd * kv_bytes)
    return flops, data


def prefill_attention_work(n: dict, prompt_len: int, act_bytes: int
                           ) -> tuple[int, int]:
    """Causal flash attention over one prompt: operations of the causal
    half, and bytes of q, k, v read and the output written, summed over
    layers."""
    L, H, Hkv, hd = n["L"], n["H"], n["Hkv"], n["hd"]
    s = prompt_len
    flops = 4 * L * H * hd * s * (s + 1) // 2
    data = L * s * (2 * H + 2 * Hkv) * hd * act_bytes
    return flops, data


@dataclasses.dataclass(frozen=True)
class DenseWork:
    """The dense family's work model, at sizes ``n``: what the per-layer
    readers ask of every family's ``work(cfg)``."""
    n: dict

    def token_flops(self, keys: int) -> int:
        return token_flops(self.n, keys)

    def prefill_flops(self, prompt_len: int) -> int:
        return prefill_flops(self.n, prompt_len)

    def decode_attention_work(self, keys: int, kv_bytes: int
                              ) -> tuple[int, int]:
        return decode_attention_work(self.n, keys, kv_bytes)

    def prefill_attention_work(self, prompt_len: int, act_bytes: int
                               ) -> tuple[int, int]:
        return prefill_attention_work(self.n, prompt_len, act_bytes)
