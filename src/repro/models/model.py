"""Model assembly: init / forward / loss / prefill / decode for all families.

Layer stacks are ``jax.lax.scan``-ed over stacked params so HLO size and
compile time are depth-independent; heterogeneous archs scan *super-blocks*:

  family      segments
  ----------  -----------------------------------------------------------
  dense       [stack: attn_mlp × L]                (qwen3, stablelm, internvl2 LM)
  gemma       [super: (5×local + 1×global) × L//6, rem: local × (L mod 6)]
  moe         [dense0 × n_dense (unrolled), stack: attn_moe × (L - n_dense)]
  ssm         [stack: ssm × L]                     (mamba2)
  zamba       [super: (6×ssm + shared attn_mlp) × L//6, rem: ssm × (L mod 6)]
  whisper     encoder [enc × L_enc] + decoder [cross × L]

Caches mirror the param tree; decode positions are per-sequence ``(B,)``
(continuous batching decodes ragged slots in lockstep HLO).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import checkpoint_policies as _cp


def _remat(fn, policy: str):
    if policy == "collectives":
        return jax.checkpoint(fn, policy=_cp.save_only_these_names(
            "attn_out", "mlp_out", "moe_out", "ssm_out"))
    return jax.checkpoint(fn)

from repro.configs.base import ArchConfig
from repro.models import blocks
from repro.models.layers import (constrain_batch, embed_fwd, init_embed,
                                 init_linear, init_norm, linear_fwd,
                                 norm_fwd, truncated_normal)

Params = Any
Cache = Any


def family(cfg: ArchConfig) -> str:
    if cfg.arch_type == "audio":
        return "whisper"
    if cfg.arch_type == "hybrid":
        return "zamba"
    if cfg.arch_type == "ssm":
        return "ssm"
    if cfg.is_moe:
        return "moe"
    if cfg.local_global_pattern:
        return "gemma"
    return "dense"  # incl. vlm (vision prefix handled at embed time)


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    out = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    if d % 2:
        out = jnp.pad(out, [(0, 0)] * (out.ndim - 1) + [(0, 1)])
    return out


def _stacked_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# page pools of a paged attention or latent group (models/cache.py)
_POOLS = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages",
          "ckv_pages", "k_rope_pages")


def _pool_groups(tree, path=()) -> list[tuple]:
    """Paths of the paged attention and latent groups in a cache
    subtree."""
    if not isinstance(tree, dict):
        return []
    if "k_pages" in tree or "ckv_pages" in tree:
        return [path]
    return [q for k, v in tree.items() for q in _pool_groups(v, path + (k,))]


def _expert_groups(tree, path=()) -> list[tuple]:
    """Paths of the MoE layers (a router beside its experts) in a params
    subtree."""
    if not isinstance(tree, dict):
        return []
    if "experts" in tree and "router" in tree:
        return [path]
    return [q for k, v in tree.items()
            for q in _expert_groups(v, path + (k,))]


def _whole_experts(params):
    """Split a layer stack's params into what a scan slices per layer and
    the MoE layers' expert stacks, which it should not: the grouped
    expert kernel reads a layer's experts where they lie in the stack
    (a slice would be a copy of the layer's slab). Returns the sliced
    part and ``inject(p, i)``, which puts each whole stack back into a
    layer's params with the layer's index under ``"layer"``."""
    paths = _expert_groups(params)
    whole, xs = {}, params
    for path in paths:
        g = _at(params, path)
        whole[path] = g["experts"]
        xs = _put(xs, path, {k: v for k, v in g.items() if k != "experts"})

    def inject(p, i):
        for path in paths:
            p = _put(p, path, {**_at(p, path), "experts": whole[path],
                               "layer": i})
        return p
    return xs, inject


def _at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _put(tree, path, value):
    if not path:
        return value
    return {**tree, path[0]: _put(tree[path[0]], path[1:], value)}


def _without_pools(group: dict) -> dict:
    return {k: v for k, v in group.items()
            if k not in _POOLS and k != "layer"}


def _layer_scan(step, x, params, cache):
    """``lax.scan`` of ``step(h, p, c) -> (h, c)`` over a decode layer
    stack, with the page pools of every paged attention group in the
    CARRY instead of xs/ys. ``step`` sees each group's whole pool,
    flattened to ``(layers, P+1, bs, ·)``, and its own layer's index
    under ``"layer"``; attention writes its token there in place and the
    kernel reads the pool where it lies. In xs/ys the scan would slice
    each layer's pool out and stack it back: two copies of the pool per
    layer per token. The MoE layers' expert stacks are passed whole in
    the same way (``_whole_experts``). A group that an enclosing scan
    already carries (the inner stack of a super-block) keeps its flat
    pool, and its layers are indexed from the ``"layer"`` it was
    given."""
    paths = _pool_groups(cache)
    n = jax.tree.leaves(params)[0].shape[0]
    params, inject = _whole_experts(params)
    pools, base, stride, xs_cache = {}, {}, {}, cache
    for path in paths:
        g = _at(cache, path)
        sdims = g["table"].ndim - 2             # stack axes, this scan's first
        stride[path] = math.prod(g["table"].shape[1:sdims])
        base[path] = g.get("layer", 0)
        pools[path] = {k: (g[k] if "layer" in g else
                           g[k].reshape((-1,) + g[k].shape[sdims:]))
                       for k in _POOLS if k in g}
        xs_cache = _put(xs_cache, path, _without_pools(g))

    def body(carry, xs):
        h, pools = carry
        p, c, i = xs
        p = inject(p, i)
        for path in paths:
            c = _put(c, path, {**_at(c, path), **pools[path],
                               "layer": base[path] + i * stride[path]})
        h, c = step(h, p, c)
        new = {}
        for path in paths:
            g = _at(c, path)
            new[path] = {k: g[k] for k in _POOLS if k in g}
            c = _put(c, path, _without_pools(g))
        return (h, new), c

    (x, pools), ys = jax.lax.scan(body, (x, pools),
                                  (params, xs_cache, jnp.arange(n)))
    for path in paths:
        g = _at(cache, path)
        if "layer" in g:
            out = {**pools[path], "layer": g["layer"]}
        else:
            out = {k: v.reshape(g[k].shape) for k, v in pools[path].items()}
        ys = _put(ys, path, {**_at(ys, path), **out})
    return x, ys


class Model:
    """Functional model wrapper: all methods are pure (jit/pjit friendly)."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.fam = family(cfg)
        if self.fam in ("gemma", "zamba"):
            per = (cfg.local_global_pattern + 1 if self.fam == "gemma"
                   else cfg.shared_attn_every)
            self.super_len = per
            self.n_super = cfg.n_layers // per
            self.n_rem = cfg.n_layers - self.n_super * per
        elif self.fam == "moe":
            self.n_dense = cfg.n_dense_layers
            self.n_moe = cfg.n_layers - self.n_dense

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        p: dict = {"embed": init_embed(keys[0], cfg.vocab_size, cfg.d_model,
                                       dtype),
                   "final_norm": init_norm(cfg, cfg.d_model, dtype)}
        if not cfg.tie_embeddings:
            p["lm_head"] = init_linear(keys[1], cfg.d_model, cfg.vocab_size,
                                       dtype)
        if cfg.n_vision_tokens:
            p["vis_proj"] = {
                "w1": init_linear(keys[2], cfg.vision_embed_dim, cfg.d_model,
                                  dtype),
                "w2": init_linear(keys[3], cfg.d_model, cfg.d_model, dtype),
            }
        fam = self.fam
        if fam == "dense":
            p["stack"] = _stacked_init(
                lambda k: blocks.init_attn_mlp(k, cfg, dtype), keys[4],
                cfg.n_layers)
        elif fam == "gemma":
            def init_super(k):
                kl, kg = jax.random.split(k)
                return {
                    "local": _stacked_init(
                        lambda kk: blocks.init_attn_mlp(kk, cfg, dtype), kl,
                        self.super_len - 1),
                    "global": blocks.init_attn_mlp(kg, cfg, dtype),
                }
            p["super"] = _stacked_init(init_super, keys[4], self.n_super)
            if self.n_rem:
                p["rem"] = _stacked_init(
                    lambda k: blocks.init_attn_mlp(k, cfg, dtype), keys[5],
                    self.n_rem)
        elif fam == "moe":
            if self.n_dense:
                p["dense0"] = _stacked_init(
                    lambda k: blocks.init_attn_mlp(k, cfg, dtype), keys[5],
                    self.n_dense)
            p["stack"] = _stacked_init(
                lambda k: blocks.init_attn_moe(k, cfg, dtype), keys[4],
                self.n_moe)
        elif fam == "ssm":
            p["stack"] = _stacked_init(
                lambda k: blocks.init_ssm_block(k, cfg, dtype), keys[4],
                cfg.n_layers)
        elif fam == "zamba":
            def init_super(k):
                return {"ssm": _stacked_init(
                    lambda kk: blocks.init_ssm_block(kk, cfg, dtype), k,
                    self.super_len)}
            p["super"] = _stacked_init(init_super, keys[4], self.n_super)
            p["shared"] = blocks.init_attn_mlp(keys[5], cfg, dtype,
                                               use_mla=False)
            if self.n_rem:
                p["rem"] = _stacked_init(
                    lambda k: blocks.init_ssm_block(k, cfg, dtype), keys[6],
                    self.n_rem)
        elif fam == "whisper":
            p["enc_stack"] = _stacked_init(
                lambda k: blocks.init_encoder_block(k, cfg, dtype), keys[4],
                cfg.n_encoder_layers)
            p["enc_norm"] = init_norm(cfg, cfg.d_model, dtype)
            p["stack"] = _stacked_init(
                lambda k: blocks.init_cross_block(k, cfg, dtype), keys[5],
                cfg.n_layers)
        else:
            raise ValueError(fam)
        return p

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------
    def _embed(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        x = embed_fwd(params["embed"], batch["tokens"])
        if cfg.n_vision_tokens:
            v = linear_fwd(params["vis_proj"]["w1"], batch["vision_embeds"])
            v = jax.nn.gelu(v)
            v = linear_fwd(params["vis_proj"]["w2"], v).astype(x.dtype)
            x = jnp.concatenate([v, x], axis=1)
        if cfg.pos_embed == "learned":  # sinusoidal absolute (whisper)
            S = x.shape[1]
            x = x + _sinusoid(jnp.arange(S), cfg.d_model).astype(x.dtype)
        return constrain_batch(x)

    def _head(self, params: Params, x: jax.Array) -> jax.Array:
        x = norm_fwd(self.cfg, params["final_norm"], x)
        if self.cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", x, params["embed"]["table"])
        return linear_fwd(params["lm_head"], x)

    def _encode(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        frames = batch["audio_frames"]
        x = frames + _sinusoid(jnp.arange(frames.shape[1]),
                               cfg.d_model).astype(frames.dtype)

        def step(carry, p):
            return blocks.encoder_fwd(p, cfg, carry), None

        x, _ = jax.lax.scan(step, x, params["enc_stack"])
        return norm_fwd(cfg, params["enc_norm"], x)

    # ------------------------------------------------------------------
    # full forward (training / eval)
    # ------------------------------------------------------------------
    def forward(self, params: Params, batch: dict, remat: bool = False,
                train: bool = False,
                remat_policy: str = "none") -> tuple[jax.Array, jax.Array]:
        """Returns (logits, moe_aux_loss). ``remat_policy="collectives"``
        saves the per-block attention/MLP/MoE/SSM outputs (checkpoint_name
        markers in blocks.py) so the backward pass does NOT recompute the
        row-parallel all-reduces — §Perf iteration: trades ~2 activations/
        layer of HBM for a third of the collective wire."""
        cfg, fam = self.cfg, self.fam
        aux = jnp.zeros((), jnp.float32)
        if fam == "whisper":
            memory = self._encode(params, batch)
            x = self._embed(params, batch)

            def step(carry, p):
                return blocks.cross_fwd(p, cfg, carry, memory), None

            body = _remat(step, remat_policy) if remat else step
            x, _ = jax.lax.scan(body, x, params["stack"])
            return self._head(params, x), aux

        x = self._embed(params, batch)
        if fam == "dense":
            def step(carry, p):
                return blocks.attn_mlp_fwd(p, cfg, carry,
                                           window=cfg.sliding_window), None
            body = _remat(step, remat_policy) if remat else step
            x, _ = jax.lax.scan(body, x, params["stack"])
        elif fam == "gemma":
            def super_step(carry, p):
                def local_step(c, pl_):
                    return blocks.attn_mlp_fwd(
                        pl_, cfg, c, window=cfg.sliding_window), None
                c, _ = jax.lax.scan(local_step, carry, p["local"])
                c = blocks.attn_mlp_fwd(p["global"], cfg, c, window=0)
                return c, None
            body = _remat(super_step, remat_policy) if remat else super_step
            x, _ = jax.lax.scan(body, x, params["super"])
            if self.n_rem:
                def rem_step(c, pl_):
                    return blocks.attn_mlp_fwd(
                        pl_, cfg, c, window=cfg.sliding_window), None
                x, _ = jax.lax.scan(rem_step, x, params["rem"])
        elif fam == "moe":
            if self.n_dense:
                def d_step(carry, p):
                    return blocks.attn_mlp_fwd(
                        p, cfg, carry, window=cfg.sliding_window), None
                x, _ = jax.lax.scan(d_step, x, params["dense0"])

            def m_step(carry, p):
                h, a = carry
                h, ax = blocks.attn_moe_fwd(p, cfg, h,
                                            window=cfg.sliding_window,
                                            train=train)
                return (h, a + ax), None
            body = _remat(m_step, remat_policy) if remat else m_step
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["stack"])
        elif fam == "ssm":
            def step(carry, p):
                return blocks.ssm_fwd(p, cfg, carry), None
            body = _remat(step, remat_policy) if remat else step
            x, _ = jax.lax.scan(body, x, params["stack"])
        elif fam == "zamba":
            shared = params["shared"]

            def super_step(carry, p):
                def s_step(c, ps):
                    return blocks.ssm_fwd(ps, cfg, c), None
                c, _ = jax.lax.scan(s_step, carry, p["ssm"])
                c = blocks.attn_mlp_fwd(shared, cfg, c, window=0)
                return c, None
            body = _remat(super_step, remat_policy) if remat else super_step
            x, _ = jax.lax.scan(body, x, params["super"])
            if self.n_rem:
                def r_step(c, ps):
                    return blocks.ssm_fwd(ps, cfg, c), None
                x, _ = jax.lax.scan(r_step, x, params["rem"])
        else:
            raise ValueError(fam)
        return self._head(params, x), aux

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def loss(self, params: Params, batch: dict, remat: bool = False,
             remat_policy: str = "none") -> tuple[jax.Array, dict]:
        cfg = self.cfg
        logits, aux = self.forward(params, batch, remat=remat, train=True,
                                   remat_policy=remat_policy)
        if cfg.n_vision_tokens:
            logits = logits[:, cfg.n_vision_tokens:]
        tokens = batch["tokens"]
        tgt = tokens[:, 1:]
        lg = logits[:, :-1].astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        nll = logz - gold
        mask = (batch["loss_mask"][:, 1:] if "loss_mask" in batch
                else jnp.ones_like(tgt)).astype(jnp.float32)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = loss + cfg.router_aux_loss_coef * aux
        return total, {"nll": loss, "moe_aux": aux,
                       "tokens": jnp.sum(mask)}

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def _stack_zeros(self, proto, n: int):
        # replicate the proto across a layer axis. Dense protos are
        # zero-filled so this equals stacking zeros; paged block tables
        # must keep their scratch-page fill, which plain zeros would
        # silently turn into "everyone shares physical page 0".
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), proto)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   layout=None) -> Cache:
        """``layout`` (a models.cache.PagedLayout) switches every pageable
        layer group to the block/paged cache; None keeps the dense rows."""
        cfg, fam = self.cfg, self.fam
        mk = functools.partial(blocks.init_block_cache, cfg, batch=batch,
                               max_len=max_len, dtype=dtype, layout=layout)
        if fam == "dense":
            return {"stack": self._stack_zeros(
                mk("attn", window=cfg.sliding_window), cfg.n_layers)}
        if fam == "gemma":
            local = mk("attn", window=cfg.sliding_window)
            glob = mk("attn", window=0)
            c = {"super": {
                "local": self._stack_zeros(
                    self._stack_zeros(local, self.super_len - 1), self.n_super),
                "global": self._stack_zeros(glob, self.n_super)}}
            if self.n_rem:
                c["rem"] = self._stack_zeros(local, self.n_rem)
            return c
        if fam == "moe":
            kind = "mla" if cfg.mla else "attn"
            c = {"stack": self._stack_zeros(
                mk(kind, window=cfg.sliding_window), self.n_moe)}
            if self.n_dense:
                c["dense0"] = self._stack_zeros(
                    mk(kind, window=cfg.sliding_window), self.n_dense)
            return c
        if fam == "ssm":
            return {"stack": self._stack_zeros(mk("ssm"), cfg.n_layers)}
        if fam == "zamba":
            c = {"super": {
                "ssm": self._stack_zeros(
                    self._stack_zeros(mk("ssm"), self.super_len), self.n_super),
                "shared": self._stack_zeros(mk("attn"), self.n_super)}}
            if self.n_rem:
                c["rem"] = self._stack_zeros(mk("ssm"), self.n_rem)
            return c
        if fam == "whisper":
            return {"stack": self._stack_zeros(mk("cross"), cfg.n_layers)}
        raise ValueError(fam)

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    @staticmethod
    def _sel(x: jax.Array, logits_at) -> jax.Array:
        """Select the hidden state the head runs on: a shared position
        (int) or one position per sequence ((B,) array — bucket-batched
        prefill, where same-bucket prompts have different real lengths)."""
        if isinstance(logits_at, int):
            return x[:, logits_at]
        idx = jnp.asarray(logits_at, jnp.int32)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]

    def prefill(self, params: Params, batch: dict, cache: Cache,
                logits_at: int | jax.Array = -1) -> tuple[jax.Array, Cache]:
        """Returns (logits (B, V) at ``logits_at``, filled cache); serving
        passes the last *real* (pre-padding) prompt position — scalar, or
        per-sequence (B,) when a padded bucket batches ragged prompts."""
        cfg, fam = self.cfg, self.fam
        if fam == "whisper":
            memory = self._encode(params, batch)
            x = self._embed(params, batch)

            def step(carry, pc):
                p, c = pc
                h, nc = blocks.cross_prefill(p, cfg, carry, memory, c)
                return h, nc

            x, ncache = jax.lax.scan(step, x, (params["stack"],
                                               cache["stack"]))
            return self._head(params, self._sel(x, logits_at)), {"stack": ncache}

        x = self._embed(params, batch)
        new_cache: dict = {}
        if fam in ("dense", "moe"):
            if fam == "moe" and self.n_dense:
                def d_step(carry, pc):
                    p, c = pc
                    h, nc = blocks.attn_mlp_prefill(
                        p, cfg, carry, c, window=cfg.sliding_window)
                    return h, nc
                x, nd = jax.lax.scan(d_step, x, (params["dense0"],
                                                 cache["dense0"]))
                new_cache["dense0"] = nd
            if fam == "moe":
                stack, inject = _whole_experts(params["stack"])

                def m_step(carry, pci):
                    p, c, i = pci
                    return blocks.attn_moe_prefill(
                        inject(p, i), cfg, carry, c,
                        window=cfg.sliding_window)
                x, ns = jax.lax.scan(m_step, x, (stack, cache["stack"],
                                                 jnp.arange(self.n_moe)))
            else:
                def step(carry, pc):
                    p, c = pc
                    h, nc = blocks.attn_mlp_prefill(
                        p, cfg, carry, c, window=cfg.sliding_window)
                    return h, nc
                x, ns = jax.lax.scan(step, x, (params["stack"],
                                               cache["stack"]))
            new_cache["stack"] = ns
        elif fam == "gemma":
            def super_step(carry, pc):
                p, c = pc

                def l_step(cc, plc):
                    pl_, cl = plc
                    h, nc = blocks.attn_mlp_prefill(
                        pl_, cfg, cc, cl, window=cfg.sliding_window)
                    return h, nc
                h, nl = jax.lax.scan(l_step, carry, (p["local"], c["local"]))
                h, ng = blocks.attn_mlp_prefill(p["global"], cfg, h,
                                                c["global"], window=0)
                return h, {"local": nl, "global": ng}
            x, nsuper = jax.lax.scan(super_step, x,
                                     (params["super"], cache["super"]))
            new_cache["super"] = nsuper
            if self.n_rem:
                def r_step(cc, plc):
                    pl_, cl = plc
                    h, nc = blocks.attn_mlp_prefill(
                        pl_, cfg, cc, cl, window=cfg.sliding_window)
                    return h, nc
                x, nr = jax.lax.scan(r_step, x, (params["rem"], cache["rem"]))
                new_cache["rem"] = nr
        elif fam == "ssm":
            def step(carry, p):
                return blocks.ssm_prefill(p, cfg, carry)
            x, ns = jax.lax.scan(step, x, params["stack"])
            new_cache["stack"] = ns
        elif fam == "zamba":
            shared = params["shared"]

            def super_step(carry, pc):
                p, c = pc

                def s_step(cc, ps):
                    return blocks.ssm_prefill(ps, cfg, cc)
                h, nssm = jax.lax.scan(s_step, carry, p["ssm"])
                h, nsh = blocks.attn_mlp_prefill(shared, cfg, h, c["shared"],
                                                 window=0)
                return h, {"ssm": nssm, "shared": nsh}
            x, nsuper = jax.lax.scan(super_step, x,
                                     (params["super"], cache["super"]))
            new_cache["super"] = nsuper
            if self.n_rem:
                def r_step(cc, ps):
                    return blocks.ssm_prefill(ps, cfg, cc)
                x, nr = jax.lax.scan(r_step, x, params["rem"])
                new_cache["rem"] = nr
        else:
            raise ValueError(fam)
        return self._head(params, self._sel(x, logits_at)), new_cache

    def prefill_suffix(self, params: Params, batch: dict, cache: Cache,
                       ctx: dict, offset: int,
                       logits_at: int | jax.Array = -1
                       ) -> tuple[jax.Array, Cache]:
        """Prefill only the residual suffix of prompts whose first
        ``offset`` positions are prefix-cache hits: ``ctx`` mirrors the
        cache tree with per-group ``{"k", "v"}`` context of width exactly
        ``offset`` (gathered from the shared pages, ``(…, B, offset,
        Hkv·hd)``), ``batch["tokens"]``
        holds the suffix tokens, and the returned mini-cache covers the
        suffix positions only (``insert`` lands it at ``offset``).
        Restricted to the sharing-eligible families — dense/moe, non-MLA,
        full-horizon rope attention, text-only suffix (the engine's gate;
        vision/audio prefixes are inside the shared ``offset``)."""
        cfg, fam = self.cfg, self.fam
        if fam not in ("dense", "moe") or cfg.mla:
            raise ValueError(f"prefix sharing unsupported for {fam}")
        x = constrain_batch(embed_fwd(params["embed"], batch["tokens"]))
        new_cache: dict = {}
        if fam == "moe" and self.n_dense:
            def d_step(carry, pcc):
                p, c, ck, cv = pcc
                h, nc = blocks.attn_mlp_suffix_prefill(p, cfg, carry, c,
                                                       ck, cv, offset)
                return h, nc
            x, nd = jax.lax.scan(d_step, x, (params["dense0"],
                                             cache["dense0"],
                                             ctx["dense0"]["k"],
                                             ctx["dense0"]["v"]))
            new_cache["dense0"] = nd
        fwd = (blocks.attn_moe_suffix_prefill if fam == "moe"
               else blocks.attn_mlp_suffix_prefill)

        def step(carry, pcc):
            p, c, ck, cv = pcc
            h, nc = fwd(p, cfg, carry, c, ck, cv, offset)
            return h, nc
        x, ns = jax.lax.scan(step, x, (params["stack"], cache["stack"],
                                       ctx["stack"]["k"],
                                       ctx["stack"]["v"]))
        new_cache["stack"] = ns
        return self._head(params, self._sel(x, logits_at)), new_cache

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(self, params: Params, tokens: jax.Array, cache: Cache,
                    pos: jax.Array) -> tuple[jax.Array, Cache]:
        """tokens: (B, 1); pos: (B,) current absolute positions.
        Returns (logits (B, V), new cache)."""
        logits, cache, _ = self._decode_step(params, tokens, cache, pos)
        return logits, cache

    def _decode_step(self, params: Params, tokens: jax.Array, cache: Cache,
                     pos: jax.Array):
        """``decode_step`` plus, for MoE families, each row's expert
        counts (B, 2) over its layers: assignments to held experts and
        those not computed (``moe.moe_serve``); None for the others."""
        cfg, fam = self.cfg, self.fam
        counts = None
        x = embed_fwd(params["embed"], tokens)
        if cfg.pos_embed == "learned":
            x = x + _sinusoid(pos[:, None], cfg.d_model).astype(x.dtype)
        new_cache: dict = {}
        if fam == "whisper":
            x, ns = _layer_scan(
                lambda h, p, c: blocks.cross_decode(p, cfg, h, c, pos), x,
                params["stack"], cache["stack"])
            return self._head(params, x[:, -1]), {"stack": ns}, counts

        def attn_mlp(h, p, c):
            return blocks.attn_mlp_decode(p, cfg, h, c, pos)

        def ssm(h, p, c):
            return blocks.ssm_decode(p, cfg, h, c, pos)

        if fam in ("dense", "moe"):
            if fam == "moe" and self.n_dense:
                x, new_cache["dense0"] = _layer_scan(
                    attn_mlp, x, params["dense0"], cache["dense0"])
            if fam == "moe":
                def moe(hn, p, c):
                    h, n = hn
                    h, c, cnt = blocks.attn_moe_decode(p, cfg, h, c, pos)
                    return (h, n + cnt.reshape(n.shape)), c
                (x, counts), new_cache["stack"] = _layer_scan(
                    moe, (x, jnp.zeros((x.shape[0], 2), jnp.int32)),
                    params["stack"], cache["stack"])
            else:
                x, new_cache["stack"] = _layer_scan(
                    lambda h, p, c: blocks.attn_mlp_decode(p, cfg, h, c,
                                                           pos),
                    x, params["stack"], cache["stack"])
        elif fam == "gemma":
            def super_step(h, p, c):
                h, nl = _layer_scan(attn_mlp, h, p["local"], c["local"])
                h, ng = attn_mlp(h, p["global"], c["global"])
                return h, {"local": nl, "global": ng}
            x, new_cache["super"] = _layer_scan(super_step, x,
                                                params["super"],
                                                cache["super"])
            if self.n_rem:
                x, new_cache["rem"] = _layer_scan(attn_mlp, x,
                                                  params["rem"],
                                                  cache["rem"])
        elif fam == "ssm":
            x, new_cache["stack"] = _layer_scan(ssm, x, params["stack"],
                                                cache["stack"])
        elif fam == "zamba":
            shared = params["shared"]

            def super_step(h, p, c):
                h, nssm = _layer_scan(ssm, h, p["ssm"], c["ssm"])
                h, nsh = attn_mlp(h, shared, c["shared"])
                return h, {"ssm": nssm, "shared": nsh}
            x, new_cache["super"] = _layer_scan(super_step, x,
                                                params["super"],
                                                cache["super"])
            if self.n_rem:
                x, new_cache["rem"] = _layer_scan(ssm, x, params["rem"],
                                                  cache["rem"])
        else:
            raise ValueError(fam)
        return self._head(params, x[:, -1]), new_cache, counts

    # ------------------------------------------------------------------
    # fused multi-token decode
    # ------------------------------------------------------------------
    def decode_chunk(self, params: Params, cache: Cache, state: dict,
                     n_tokens: int, *, max_len: int,
                     greedy: bool = True) -> tuple[jax.Array, jax.Array,
                                                   dict, Cache]:
        """Fused decode of ``n_tokens`` steps: one ``lax.scan`` over the
        per-token ``decode_step`` body with sampling (argmax or
        PRNG-carried categorical), per-slot bookkeeping and stop
        conditions all inside the graph — one XLA dispatch and one host
        transfer per *chunk* instead of per token.

        ``state`` carries the per-slot decode state:
          tokens    (B,) int32  last sampled token per slot
          pos       (B,) int32  next cache write position per slot
          remaining (B,) int32  tokens still to emit per slot
          active    (B,) bool   slot is mid-generation
          key       PRNG key    sampling state (advanced when not greedy)

        A slot emits one token per step while active; it deactivates
        in-graph once ``remaining`` hits 0 or ``pos`` reaches
        ``max_len - 1`` (mid-chunk finishes), after which its state is
        frozen and further steps write only ignorable garbage into its
        (about-to-be-re-prefilled) cache row — the same contract the
        per-token engine path has for idle slots.

        Returns ``(tokens (B, n_tokens), emitted (B,), new_state,
        new_cache)``; per slot, only the first ``emitted`` tokens of its
        row are real. For MoE families ``new_state["moe_counts"]`` (2,)
        sums the active rows' expert counts over the chunk
        (``_decode_step``). Jit this with ``donate_argnums`` on ``cache`` for a
        copy-free decode: the token scan carries the donated cache, and
        each layer scan carries the paged pools (``_layer_scan``), so a
        token's K/V row is written into the donated buffer in place and
        the paged kernel reads it there — no copy of the pool follows
        either loop. Dense rings still ride the layer scan's xs/ys.
        """
        moe = self.fam == "moe"

        def step(carry, _):
            cache, tok, pos, rem, act, key = carry[:6]
            logits, cache, counts = self._decode_step(params, tok[:, None],
                                                      cache, pos)
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits).astype(jnp.int32)
            nxt = jnp.where(act, nxt, tok)
            pos = jnp.where(act, pos + 1, pos)
            rem = jnp.where(act, rem - 1, rem)
            nact = act & (rem > 0) & (pos < max_len - 1)
            out = (cache, nxt, pos, rem, nact, key)
            if moe:   # the active rows' expert counts, summed
                out += (carry[6] + jnp.sum(
                    jnp.where(act[:, None], counts, 0), axis=0),)
            return out, (nxt, act)

        carry = (cache, state["tokens"], state["pos"], state["remaining"],
                 state["active"], state["key"])
        if moe:
            carry += (jnp.zeros((2,), jnp.int32),)
        carry, (toks, emits) = jax.lax.scan(step, carry, None,
                                            length=n_tokens)
        cache, tok, pos, rem, act, key = carry[:6]
        new_state = {"tokens": tok, "pos": pos, "remaining": rem,
                     "active": act, "key": key}
        if moe:
            new_state["moe_counts"] = carry[6]
        return (jnp.swapaxes(toks, 0, 1),
                jnp.sum(emits.astype(jnp.int32), axis=0), new_state, cache)
