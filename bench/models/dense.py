"""The dense family: decoder-only transformers with grouped-query
attention and a gated MLP. Its shapes, the program's configuration and
parameter tree, its work and the plain float32 reference.

The benchmark makes the weights, not the program: ``init_weights`` draws
them from the run's seed in a canonical layout (one stacked array per
kind of weight, Hugging Face's naming and order), ``to_program`` rearranges
that tree into the serving program's parameter tree, and ``Reference``
is the reference: the published equations in plain ``jax.numpy``, float32
at ``highest`` matmul precision, one layer at a time, with no kernel,
cache or batching (``fp8=True`` makes it the control, every matmul's
operands rounded to float8 e4m3). It imports nothing of the program
(``program_config`` and ``to_program`` touch the program's types only
through arguments and a local import) and reads nothing the program
made: ``reference_weights`` draws the same canonical tree again from the
seed.

Every function takes the family's ``flags``: the norm kind and whether q
and k are normalised per head; the rest (tied head, partial rotary,
sizes) is read from the config. A model file that names a dense model
(``bench/models/qwen3.py``) binds its flags and exports the contract of
``bench/spec.py``; the harness never calls this file directly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.common import draw_tree, mm, seed_key
from bench.work import DenseWork


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def _shapes(cfg: dict, flags: dict) -> dict:
    n = dims(cfg)
    L, d, H, Hkv, hd, ff, V = (n[k] for k in
                               ("L", "d", "H", "Hkv", "hd", "ff", "V"))
    norm = {"scale": (d,)}
    if flags["norm"] == "layernorm":
        norm["bias"] = (d,)
    layers = {"ln1": {k: (L,) + s for k, s in norm.items()},
              "ln2": {k: (L,) + s for k, s in norm.items()},
              "wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
              "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d),
              "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if flags["qk_norm"]:
        layers["q_norm"] = (L, hd)
        layers["k_norm"] = (L, hd)
    out = {"embed": (V, d), "final_norm": dict(norm), "layers": layers}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    return out


def init_weights(cfg: dict, flags: dict, key: jax.Array,
                 dtype=jnp.bfloat16) -> dict:
    """The canonical tree drawn from ``key`` (``common.draw_tree``).
    Call under ``jax.jit`` so that the tree is made on the device in one
    program."""
    return draw_tree(_shapes(cfg, flags), key, dtype)


def reference_weights(cfg: dict, flags: dict, seed: int) -> dict:
    """The canonical tree drawn again from the seed, as float32 holding
    the bfloat16 values the program was served."""
    w = jax.jit(lambda k: init_weights(cfg, flags, k))(seed_key(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def work(cfg: dict) -> DenseWork:
    """The family's work model: ``bench/work.py``'s dense formulas at this
    configuration's sizes."""
    return DenseWork(dims(cfg))


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def program_config(cfg: dict, flags: dict, name: str):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.configs.base import ArchConfig
    if cfg.get("use_qkv_bias") or cfg.get("attention_bias"):
        raise ValueError("q/k/v projection biases: neither the program "
                         "nor this reference has them")
    n = dims(cfg)
    eps_key = ("rms_norm_eps" if flags["norm"] == "rmsnorm"
               else "layer_norm_eps")
    return ArchConfig(
        name=name, arch_type="dense", source=cfg["source"],
        n_layers=n["L"], d_model=n["d"], n_heads=n["H"],
        n_kv_heads=n["Hkv"], head_dim=n["hd"], d_ff=n["ff"],
        vocab_size=n["V"], qk_norm=flags["qk_norm"],
        rope_theta=float(cfg["rope_theta"]),
        partial_rotary_factor=float(cfg.get("partial_rotary_factor", 1.0)),
        norm_eps=float(cfg[eps_key]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        act="silu", norm_type=flags["norm"], mlp_gated=True,
        pos_embed="rope")


def to_program(w: dict, cfg: dict, flags: dict) -> dict:
    """The canonical tree rearranged into the program's parameter tree
    (``repro.models.Model.init`` layout for the dense family)."""
    n = dims(cfg)
    L, d, H, Hkv, hd = n["L"], n["d"], n["H"], n["Hkv"], n["hd"]
    lw = w["layers"]
    attn = {"wq": lw["wq"].reshape(L, d, H, hd),
            "wk": lw["wk"].reshape(L, d, Hkv, hd),
            "wv": lw["wv"].reshape(L, d, Hkv, hd),
            "wo": lw["wo"].reshape(L, H, hd, d)}
    if flags["qk_norm"]:
        attn["q_norm"] = {"scale": lw["q_norm"]}
        attn["k_norm"] = {"scale": lw["k_norm"]}
    p = {"embed": {"table": w["embed"]},
         "final_norm": dict(w["final_norm"]),
         "stack": {"ln1": dict(lw["ln1"]), "ln2": dict(lw["ln2"]),
                   "attn": attn,
                   "mlp": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                           "w_down": lw["w_down"]}}}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, positions, theta: float, partial: float):
    """Rotary embedding on the first ``partial`` of each head, by rotating
    its two halves (Hugging Face's ``rotate_half``). x: (S, heads, hd)."""
    hd = x.shape[-1]
    rot = int(hd * partial) // 2 * 2
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _layer(cfg: dict, flags: dict, w: dict, x: jax.Array,
           fp8: bool) -> jax.Array:
    """One decoder layer over a whole sequence x: (S, d), causal."""
    n = dims(cfg)
    H, Hkv, hd = n["H"], n["Hkv"], n["hd"]
    eps = float(cfg["rms_norm_eps" if flags["norm"] == "rmsnorm"
                    else "layer_norm_eps"])
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _norm(x, w["ln1"], flags["norm"], eps)
    q = mm(h, w["wq"], fp8).reshape(S, H, hd)
    k = mm(h, w["wk"], fp8).reshape(S, Hkv, hd)
    v = mm(h, w["wv"], fp8).reshape(S, Hkv, hd)
    if flags["qk_norm"]:
        q = q / jnp.sqrt((q * q).mean(-1, keepdims=True) + eps) * w["q_norm"]
        k = k / jnp.sqrt((k * k).mean(-1, keepdims=True) + eps) * w["k_norm"]
    theta = float(cfg["rope_theta"])
    partial = float(cfg.get("partial_rotary_factor", 1.0))
    q, k = _rope(q, pos, theta, partial), _rope(k, pos, theta, partial)
    group = H // Hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", a, v).reshape(S, H * hd)
    x = x + mm(o, w["wo"], fp8)
    h = _norm(x, w["ln2"], flags["norm"], eps)
    g = mm(h, w["w_gate"], fp8)
    u = mm(h, w["w_up"], fp8)
    return x + mm(jax.nn.silu(g) * u, w["w_down"], fp8)


def _head(cfg: dict, flags: dict, w: dict, x: jax.Array, fp8: bool):
    eps = float(cfg["rms_norm_eps" if flags["norm"] == "rmsnorm"
                    else "layer_norm_eps"])
    h = _norm(x, w["final_norm"], flags["norm"], eps)
    head = w["embed"].T if "lm_head" not in w else w["lm_head"]
    return mm(h, head, fp8)


class Reference:
    """The reference forward, compiled once per sequence length: one
    program for a layer, applied layer after layer, and one for the
    head, so that what is live on the device is one layer's weights and
    the activations of one sequence."""

    def __init__(self, cfg: dict, flags: dict, weights: dict,
                 fp8: bool = False):
        self.cfg, self.flags, self.w, self.fp8 = cfg, flags, weights, fp8
        self._layer = jax.jit(lambda layers, i, x: _layer(
            cfg, flags, jax.tree.map(lambda a: a[i], layers), x, fp8))
        self._head = jax.jit(lambda w, x: _head(cfg, flags, w, x, fp8))

    def logits(self, tokens: np.ndarray) -> jax.Array:
        """Logits (S, V) at every position of one sequence."""
        with jax.default_matmul_precision("highest"):
            x = jnp.take(self.w["embed"], jnp.asarray(tokens), axis=0)
            for i in range(dims(self.cfg)["L"]):
                x = self._layer(self.w["layers"], jnp.int32(i), x)
            return self._head(self.w, x)
