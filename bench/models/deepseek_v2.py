"""DeepSeek-V2 (hf:deepseek-ai/DeepSeek-V2-Lite, ``model_type``
``deepseek_v2``): multi-head latent attention with YaRN rope, leading
dense layers, then MoE layers of routed experts beside shared ones. Its
shapes, the program's configuration and parameter tree, its work and the
plain float32 reference, as the contract of ``bench/spec.py``.

The configuration states a deployment: expert parallelism over
``deployment.expert_parallel`` chips, this chip holding
``n_routed_experts`` of the ``deployment.routed_experts`` published ones
from ``deployment.first_expert`` on; attention, the dense layers, the
router, the shared experts and the vocabulary are whole on every chip.
The router scores all the published experts, and a layer's output is
what the held experts and the shared experts give: the part the absent
experts would add is left out, here as in the program. That is the one
departure from the published model.

The weights are drawn from the run's seed in a canonical layout (Hugging
Face's names and order, each matrix as ``(in, out)``): the embedding,
head and final norm from one key, and layer ``i``'s leaves from a key
folded with ``i``, each routed expert's from that layer's expert key
folded with the expert's published index. So a layer, or one expert, can
be drawn alone, and a chip's share is the same experts whatever share it
is. ``Reference`` draws each layer as it runs (a float32 tree of the
whole model would be 12.4 GB) and computes the published equations in
plain ``jax.numpy``, float32 at ``highest``, with no kernel, cache or
batching; ``fp8=True`` rounds every matmul's operands to float8 e4m3
(the control).

Hugging Face's rope interleaves each rope pair as adjacent columns and
de-interleaves them before ``rotate_half``; the program rotates halves,
so ``to_program`` permutes the rope columns of ``q_proj`` and
``kv_a_proj_with_mqa`` to the de-interleaved order, and the reference
de-interleaves as Hugging Face does.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.common import draw_tree, mm, seed_key


def dims(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "r": cfg["kv_lora_rank"], "ff": cfg["intermediate_size"],
            "mff": cfg["moe_intermediate_size"],
            "n_dense": cfg["first_k_dense_replace"],
            "E": dep["routed_experts"], "first": dep["first_expert"],
            "n": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"], "V": cfg["vocab_size"]}


def _check(cfg: dict) -> None:
    """Refuse what neither the program nor this reference computes."""
    want = {"q_lora_rank": None, "scoring_func": "softmax",
            "topk_method": "greedy", "n_group": 1, "topk_group": 1,
            "routed_scaling_factor": 1, "moe_layer_freq": 1,
            "attention_bias": False, "hidden_act": "silu",
            "tie_word_embeddings": False}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if bad or cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"unsupported DeepSeek-V2 settings: {bad}, "
                         f"rope_scaling {cfg['rope_scaling']}")
    n = dims(cfg)
    if not 0 <= n["first"] <= n["E"] - n["n"]:
        raise ValueError(f"held experts {n['first']}..+{n['n']} lie "
                         f"outside the {n['E']} routed experts")


# ---------------------------------------------------------------------------
# the canonical tree
# ---------------------------------------------------------------------------
def _layer_shapes(n: dict, moe: bool) -> dict:
    d, H, r = n["d"], n["H"], n["r"]
    out = {"ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
           "wq": (d, H * (n["dn"] + n["dr"])),
           "w_kv_a": (d, r + n["dr"]), "kv_norm": (r,),
           "w_kv_b": (r, H * (n["dn"] + n["dv"])), "wo": (H * n["dv"], d)}
    if moe:
        sff = n["shared"] * n["mff"]
        out["router"] = (d, n["E"])
        out["shared"] = {"w_gate": (d, sff), "w_up": (d, sff),
                         "w_down": (sff, d)}
    else:
        out["mlp"] = {"w_gate": (d, n["ff"]), "w_up": (d, n["ff"]),
                      "w_down": (n["ff"], d)}
    return out


def _expert_shapes(n: dict) -> dict:
    d, f = n["d"], n["mff"]
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _keys(key: jax.Array):
    """(the key of the embedding, head and final norm, the layers' key)"""
    return tuple(jax.random.split(key))


def _draw_layer(n: dict, layer_key: jax.Array, i, moe: bool, dtype):
    """Layer ``i``'s leaves, its held experts stacked on a leading axis."""
    k_i = jax.random.fold_in(layer_key, i)
    k_own, k_exp = jax.random.split(k_i)
    w = draw_tree(_layer_shapes(n, moe), k_own, dtype)
    if moe:
        ids = jnp.arange(n["first"], n["first"] + n["n"])
        w["experts"] = jax.vmap(lambda e: draw_tree(
            _expert_shapes(n), jax.random.fold_in(k_exp, e), dtype))(ids)
    return w


def _globals(n: dict, key: jax.Array, dtype) -> dict:
    return draw_tree({"embed": (n["V"], n["d"]), "lm_head": (n["d"], n["V"]),
                      "final_norm": {"scale": (n["d"],)}}, key, dtype)


def init_weights(cfg: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """The canonical tree drawn from ``key``: the globals, and the dense
    and MoE layers each stacked on a leading axis. Call under
    ``jax.jit`` so that the tree is made on the device in one program."""
    n = dims(cfg)
    k_glob, k_layers = _keys(key)
    w = _globals(n, k_glob, dtype)
    w["dense"] = jax.vmap(lambda i: _draw_layer(
        n, k_layers, i, False, dtype))(jnp.arange(n["n_dense"]))
    w["moe"] = jax.vmap(lambda i: _draw_layer(
        n, k_layers, i, True, dtype))(jnp.arange(n["n_dense"], n["L"]))
    return w


def reference_weights(cfg: dict, seed: int) -> dict:
    """The key the canonical tree is drawn from. ``Reference`` draws
    each leaf from it as it needs it, as bfloat16 values held in
    float32 (what the program was served): a whole float32 tree would
    not fit beside the reference's work on one chip."""
    return {"key": seed_key(seed)}


def work(cfg: dict) -> "MlaMoeWork":
    return MlaMoeWork(cfg)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def program_config(cfg: dict, name: str):
    """The program's ``ArchConfig``: MLA with YaRN, the published router
    over this chip's held experts."""
    from repro.configs.base import ArchConfig, YarnScaling
    _check(cfg)
    n = dims(cfg)
    rs = cfg["rope_scaling"]
    return ArchConfig(
        name=name, arch_type="moe", source=cfg["source"],
        n_layers=n["L"], d_model=n["d"], n_heads=n["H"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=n["dn"] + n["dr"], d_ff=n["ff"], vocab_size=n["V"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        mla=True, kv_lora_rank=n["r"], qk_rope_head_dim=n["dr"],
        qk_nope_head_dim=n["dn"], v_head_dim=n["dv"],
        n_experts=n["E"], n_experts_per_tok=n["K"],
        experts_held=n["n"], first_expert=n["first"],
        n_shared_experts=n["shared"], moe_d_ff=n["mff"],
        n_dense_layers=n["n_dense"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=False,
        act="silu", norm_type="rmsnorm", mlp_gated=True, pos_embed="rope")


def rope_order(dr: int) -> np.ndarray:
    """Hugging Face's de-interleave of the rope dims: even columns, then
    odd ones."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def to_program(w: dict, cfg: dict) -> dict:
    """The canonical tree rearranged into the program's parameter tree
    (``repro.models.Model.init`` for the moe family), the rope columns
    put in the program's order."""
    n = dims(cfg)
    d, H, dn, dr, dv, r = (n[k] for k in ("d", "H", "dn", "dr", "dv", "r"))
    perm = rope_order(dr)

    def attn(lw):
        L = lw["wq"].shape[0]
        wq = lw["wq"].reshape(L, d, H, dn + dr)
        wq = jnp.concatenate([wq[..., :dn], wq[..., dn + perm]], axis=-1)
        kva = lw["w_kv_a"]
        kvb = lw["w_kv_b"].reshape(L, r, H, dn + dv)
        return {"wq": wq,
                "w_dkv": jnp.concatenate([kva[..., :r], kva[..., r + perm]],
                                         axis=-1),
                "w_uk": kvb[..., :dn], "w_uv": kvb[..., dn:],
                "wo": lw["wo"].reshape(L, H, dv, d),
                "kv_norm": {"scale": lw["kv_norm"]}}

    def layers(lw):
        return {"ln1": dict(lw["ln1"]), "ln2": dict(lw["ln2"]),
                "attn": attn(lw)}

    dense = {**layers(w["dense"]), "mlp": dict(w["dense"]["mlp"])}
    mw = w["moe"]
    moe = {**layers(mw), "moe": {"router": mw["router"].astype(jnp.float32),
                                 "experts": dict(mw["experts"]),
                                 "shared": dict(mw["shared"])}}
    return {"embed": {"table": w["embed"]},
            "final_norm": dict(w["final_norm"]),
            "lm_head": {"w": w["lm_head"]}, "dense0": dense, "stack": moe}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _rms(x, scale, eps: float):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s ``inv_freq``, in float64."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / rs["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(cfg: dict) -> float:
    """``DeepseekV2Attention.softmax_scale``."""
    rs = cfg["rope_scaling"]
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        s *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rope(x, positions, cfg: dict):
    """Hugging Face's DeepSeek-V2 rope: de-interleave the pairs, then
    ``rotate_half`` with cos/sin scaled by the mscale ratio.
    x: (S, heads, dr)."""
    rs = cfg["rope_scaling"]
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = positions[:, None].astype(jnp.float32) * inv
    emb = jnp.concatenate([ang, ang], axis=-1)
    m = (_yarn_mscale(rs["factor"], rs["mscale"])
         / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos = (jnp.cos(emb) * m)[:, None, :]
    sin = (jnp.sin(emb) * m)[:, None, :]
    x = x[..., rope_order(x.shape[-1])]
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _attention(cfg: dict, n: dict, w: dict, h, fp8: bool):
    S = h.shape[0]
    H, dn, dr, dv, r = (n[k] for k in ("H", "dn", "dr", "dv", "r"))
    eps = float(cfg["rms_norm_eps"])
    pos = jnp.arange(S)
    q = mm(h, w["wq"], fp8).reshape(S, H, dn + dr)
    kva = mm(h, w["w_kv_a"], fp8)
    ckv = _rms(kva[:, :r], w["kv_norm"], eps)
    k_pe = _rope(kva[:, None, r:], pos, cfg)                 # (S, 1, dr)
    kv = mm(ckv, w["w_kv_b"], fp8).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    s = jnp.einsum("qhk,shk->hqs", q, k) * softmax_scale(cfg)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", a, kv[..., dn:]).reshape(S, H * dv)
    return mm(o, w["wo"], fp8)


def _mlp(w: dict, h, fp8: bool):
    g = mm(h, w["w_gate"], fp8)
    u = mm(h, w["w_up"], fp8)
    return mm(jax.nn.silu(g) * u, w["w_down"], fp8)


def _moe(cfg: dict, n: dict, w: dict, h, fp8: bool):
    """The held experts' part of the routed output, plus the shared
    experts."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)         # (S, E)
    gate, idx = jax.lax.top_k(probs, n["K"])
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    ids = n["first"] + jnp.arange(n["n"])
    weight = jnp.einsum("ske,sk->se",
                        (idx[..., None] == ids).astype(jnp.float32), gate)
    out = _mlp(w["shared"], h, fp8)
    for e in range(n["n"]):
        ew = jax.tree.map(lambda a: a[e], w["experts"])
        out = out + weight[:, e:e + 1] * _mlp(ew, h, fp8)
    return out


def _layer(cfg: dict, n: dict, w: dict, x, moe: bool, fp8: bool):
    """One decoder layer over a whole sequence x: (S, d), causal."""
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(cfg, n, w, _rms(x, w["ln1"]["scale"], eps), fp8)
    h = _rms(x, w["ln2"]["scale"], eps)
    if moe:
        return x + _moe(cfg, n, w, h, fp8)
    return x + _mlp(w["mlp"], h, fp8)


class Reference:
    """The reference forward: one program per kind of layer, which draws
    the layer's weights from the seed's key and applies it, run layer
    after layer; the embedding and the head likewise. What is live on
    the device is one layer's weights and one sequence's activations."""

    def __init__(self, cfg: dict, weights: dict, fp8: bool = False):
        _check(cfg)
        self.cfg, self.n, self.fp8 = cfg, dims(cfg), fp8
        self.k_glob, self.k_layers = _keys(weights["key"])
        n = self.n

        def f32(tree):
            return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

        def layer(i, x, moe):
            w = f32(_draw_layer(n, self.k_layers, i, moe, jnp.bfloat16))
            return _layer(cfg, n, w, x, moe, fp8)
        self._dense = jax.jit(lambda i, x: layer(i, x, False))
        self._moe = jax.jit(lambda i, x: layer(i, x, True))

        def embed(tokens):
            g = f32(_globals(n, self.k_glob, jnp.bfloat16))
            return jnp.take(g["embed"], tokens, axis=0)

        def head(x):
            g = f32(_globals(n, self.k_glob, jnp.bfloat16))
            h = _rms(x, g["final_norm"]["scale"], float(cfg["rms_norm_eps"]))
            return mm(h, g["lm_head"], fp8)
        self._embed, self._head = jax.jit(embed), jax.jit(head)

    def logits(self, tokens: np.ndarray) -> jax.Array:
        """Logits (S, V) at every position of one sequence."""
        with jax.default_matmul_precision("highest"):
            x = self._embed(jnp.asarray(tokens))
            for i in range(self.n["L"]):
                fn = self._dense if i < self.n["n_dense"] else self._moe
                x = fn(jnp.int32(i), x)
            return self._head(x)


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MlaMoeWork:
    """Operations and bytes of the part of the model this chip runs: the
    whole attention, dense layers, router, shared experts and head, and
    of the routed experts only the held ones, at the expected
    ``K · n / E`` held assignments per token and layer (random routing:
    the router's choice of expert is uniform in expectation). The
    shapes are read from the configuration when a count is first asked
    for."""
    cfg: dict

    @functools.cached_property
    def n(self) -> dict:
        return dims(self.cfg)

    @property
    def _held_per_token(self) -> float:
        n = self.n
        return n["K"] * n["n"] / n["E"]

    def expert_token_flops(self) -> float:
        """Routed-expert operations of one token over the MoE layers,
        held assignments only (the grouped matmul's work)."""
        n = self.n
        return ((n["L"] - n["n_dense"]) * self._held_per_token
                * 6 * n["d"] * n["mff"])

    def expert_call_bytes(self, weight_bytes: int) -> int:
        """Bytes one grouped-matmul call reads at least: one layer's held
        experts' gate, up and down weights."""
        n = self.n
        return n["n"] * 3 * n["d"] * n["mff"] * weight_bytes

    def _matmul_flops(self) -> float:
        n = self.n
        d, H, dn, dr, dv, r = (n[k] for k in ("d", "H", "dn", "dr", "dv",
                                              "r"))
        attn = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) \
            + H * dv * d
        shared = 3 * d * n["shared"] * n["mff"]
        per_moe = attn + d * n["E"] + shared
        per_dense = attn + 3 * d * n["ff"]
        return (2 * (n["n_dense"] * per_dense
                     + (n["L"] - n["n_dense"]) * per_moe + d * n["V"])
                + self.expert_token_flops())

    def _attention_flops(self, keys: int) -> int:
        """Scores over q/k of nope + rope dims and values of ``dv``, one
        query against ``keys`` keys, over the layers (the decompressed
        count of the model's attention)."""
        n = self.n
        return 2 * n["L"] * n["H"] * (n["dn"] + n["dr"] + n["dv"]) * keys

    def token_flops(self, keys: int) -> int:
        return int(self._matmul_flops() + self._attention_flops(keys))

    def prefill_flops(self, prompt_len: int) -> int:
        s = prompt_len
        n = self.n
        return int(self._matmul_flops() * s
                   + 2 * n["L"] * n["H"] * (n["dn"] + n["dr"] + n["dv"])
                   * s * (s + 1) // 2)

    def decode_attention_work(self, keys: int, kv_bytes: int
                              ) -> tuple[int, int]:
        """The latent decode kernel for one query over ``keys`` live
        keys, over the layers: scores against the latent (r) and the
        rope key (dr) and the latent context, for every head; bytes of
        the ``r + dr`` latent elements of each live key, and of the
        latent query, rope query and latent context."""
        n = self.n
        L, H, r, dr = n["L"], n["H"], n["r"], n["dr"]
        flops = L * 2 * H * (2 * r + dr) * keys
        data = L * ((r + dr) * keys + H * (2 * r + dr)) * kv_bytes
        return flops, data

    def prefill_attention_work(self, prompt_len: int, act_bytes: int
                               ) -> tuple[int, int]:
        """Causal flash attention over one prompt, decompressed: q and k
        of ``dn + dr``, v and the output of ``dv``, every head."""
        n = self.n
        L, H, s = n["L"], n["H"], prompt_len
        dqk, dv = n["dn"] + n["dr"], n["dv"]
        flops = L * 2 * H * (dqk + dv) * s * (s + 1) // 2
        data = L * s * H * (2 * dqk + 2 * dv) * act_bytes
        return flops, data
