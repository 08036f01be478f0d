"""Mixture-of-Experts: top-k router + capacity-bounded scatter dispatch
for the full-sequence forward, and a dropless grouped dispatch over the
experts a chip holds for the serving programs (``moe_serve``).

Dispatch is position-in-expert scatter (cumsum over the one-hot expert
assignment), not the GShard dense one-hot einsum: the scatter adds zero
matmul FLOPs, so ``cost_analysis`` reflects only *useful* expert compute
(keeps the MODEL_FLOPS/HLO_FLOPs roofline ratio honest). Tokens beyond an
expert's capacity are dropped (standard capacity-factor semantics); the
router aux loss (Switch-style load balancing) is returned for training.

Under pjit the (E, C, d) buffers shard over the "model" axis — GSPMD emits
the all-to-all pair around the expert matmuls. A shard_map variant with
explicit collectives is a §Perf hillclimb, not the baseline.

``moe_serve`` is what prefill and decode run: the router scores all
``n_experts`` in float32, and only the assignments to the experts this
chip holds (``cfg.experts_held`` from ``cfg.first_expert``) are computed,
sorted by expert into row tiles and run through one grouped SwiGLU
matmul (``kernels/moe_gmm.py``), so the work follows the assignments and
none is dropped. What the experts held elsewhere would add is not
computed here: under expert parallelism it comes from their chips.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.layers import (constrain, init_mlp, mesh_axis_sizes,
                                 mlp_fwd, truncated_normal)

# rows of one tile of the grouped expert matmul: each held expert's
# assignments are padded to a whole number of tiles
TILE_ROWS = 128


def _mesh_info():
    """(data_axes, data_size, model_size) of the ambient mesh (if any)."""
    sizes = mesh_axis_sizes()
    if not sizes:
        return (), 1, 1
    dax = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    dsize = 1
    for a in dax:
        dsize *= sizes[a]
    return dax, dsize, sizes.get("model", 1)

def init_moe(key, cfg: ArchConfig, dtype) -> dict:
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    p = {
        "router": truncated_normal(ks[0], (d, cfg.n_experts), jnp.float32,
                                   d ** -0.5),
        # the held experts stacked on a leading axis
        "experts": jax.vmap(
            lambda k: init_mlp(k, d, cfg.moe_d_ff, dtype))(
                jax.random.split(ks[1], cfg.n_held)),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[2], d,
                               cfg.moe_d_ff * cfg.n_shared_experts, dtype)
    return p


def _route(p: dict, cfg: ArchConfig, xt: jax.Array):
    """Top-k of the float32 softmax over every routed expert: gates
    (T, K), expert indices (T, K) and the probabilities (T, E). The
    gates are renormalised to sum 1 only where ``norm_topk_prob``."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return gate_vals, expert_idx, probs


def moe_serve(p: dict, cfg: ArchConfig, x: jax.Array, act: str = "silu"
              ) -> tuple[jax.Array, jax.Array]:
    """The serving programs' MoE layer, dropless over the held experts.
    x: (B, S, d) -> (out (B, S, d), counts (B·S, 2) int32): per token,
    its assignments to held experts and how many of those were not
    computed (0 by construction: the tiles cover every assignment).

    Each held expert's assignments are sorted into consecutive tiles of
    ``TILE_ROWS`` rows; the grouped matmul runs the tiles in use and
    skips the rest. ``p["experts"]`` is one layer's ``(E_held, …)``
    weights, or the whole layer stack ``(L, E_held, …)`` with this
    layer's index under ``p["layer"]`` (model._layer_scan passes the
    stack whole, so that the kernel reads the experts where they lie)."""
    B, S, d = x.shape
    T = B * S
    K, n, tm = cfg.n_experts_per_tok, cfg.n_held, TILE_ROWS
    xt = x.reshape(T, d)
    gate_vals, expert_idx, _ = _route(p, cfg, xt)
    local = (expert_idx - cfg.first_expert).reshape(T * K)
    held = (local >= 0) & (local < n)
    e = jnp.where(held, local, n)                    # n: held elsewhere
    onehot = jax.nn.one_hot(e, n + 1, dtype=jnp.int32)      # (TK, n+1)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                               e[:, None], axis=1)[:, 0]
    tiles = -(-jnp.sum(onehot[:, :n], axis=0) // tm)         # (n,)
    tile_end = jnp.cumsum(tiles)
    n_tiles = tile_end[-1]
    # a token sends at most min(K, n) assignments here; each expert's
    # last tile may be partly padding
    max_tiles = -(-T * min(K, n) // tm) + n
    first_row = (tile_end - tiles)[jnp.minimum(e, n - 1)] * tm
    row = jnp.where(held, first_row + rank, max_tiles * tm)
    xs = jnp.zeros((max_tiles * tm, d), x.dtype).at[row].set(
        jnp.repeat(xt, K, axis=0), mode="drop")
    t = jnp.arange(max_tiles)
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= t[:, None], axis=1), n - 1)
    experts = p["experts"]
    layer = p.get("layer")
    if layer is None:
        experts, layer = jax.tree.map(lambda a: a[None], experts), 0
    y = kops.moe_expert_gmm(xs, tile_expert.astype(jnp.int32),
                            n_tiles.astype(jnp.int32), layer,
                            experts["w_gate"], experts["w_up"],
                            experts["w_down"], act=act)
    done = held & (row < max_tiles * tm)
    rows = jnp.where(done[:, None],
                     y[jnp.minimum(row, max_tiles * tm - 1)], 0)
    out = jnp.sum((rows.astype(jnp.float32)
                   * gate_vals.reshape(T * K, 1)).reshape(T, K, d), axis=1)
    out = out.astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + mlp_fwd(p["shared"], xt, act)
    counts = jnp.stack([held.reshape(T, K).sum(1),
                        (held & ~done).reshape(T, K).sum(1)],
                       axis=1).astype(jnp.int32)
    return out.reshape(B, S, d), counts


def _capacity(n_tokens: int, cfg: ArchConfig, train: bool) -> int:
    cf = cfg.moe_train_cf if train else cfg.moe_eval_cf
    per = n_tokens * cfg.n_experts_per_tok / cfg.n_experts
    return max(4, min(n_tokens, int(per * cf + 0.5)))


def _dispatch_shard_map(experts: dict, cfg: ArchConfig, xt: jax.Array,
                        safe_e, safe_pos, keep, gate_vals,
                        G: int, Tg: int, C: int, act: str) -> jax.Array:
    """Expert dispatch + FFN + combine in ONE shard_map region (§Perf).

    GSPMD cannot prove that a dynamic scatter into an expert-sharded buffer
    is shard-local, so it materialises partial scatters and all-reduces the
    WHOLE (E, C, d) dispatch buffer every layer. This region states the
    locality explicitly:

      * scatter: each shard writes only the rows whose expert lives in its
        model shard (E | model: expert parallelism) or all rows of its own
        token group (E ∤ model: ff-parallel experts) — zero communication;
      * expert FFN: local matmuls against the shard's weight slice (the
        FSDP'd weights are all-gathered ONCE at region entry — the classic
        per-layer FSDP gather, ~weights/model_axis per chip);
      * combine: gather + gate + top-K sum LOCALLY, then one psum over
        "model" of the (Tg, d) per-token result — K·capacity_factor× less
        wire than reducing the expert outputs row-wise.
    """
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    d = xt.shape[-1]
    dax, dsize, msize = _mesh_info()
    # experts split over "model" when they divide it (expert parallelism);
    # otherwise every model shard handles all E experts and parallelism
    # comes from the ff-sharded expert weights (mixtral: E=8 < model=16)
    expert_parallel = msize > 1 and E % msize == 0
    Eloc = E // msize if expert_parallel else E
    dentry = dax if len(dax) > 1 else dax[0]
    dspec = P(dentry)

    tok_rep = jnp.repeat(xt.reshape(G, Tg, d), K, axis=1)      # (G, TgK, d)
    gates = gate_vals.reshape(G, Tg * K)

    def _erel(e):
        if not expert_parallel:
            return e, jnp.ones(e.shape, bool)
        j = jax.lax.axis_index("model")
        e_rel = e - j * Eloc
        return e_rel, (e_rel >= 0) & (e_rel < Eloc)

    def region(experts_l, e, pp, kp, g, t):
        e_rel, ok_e = _erel(e)
        se = jnp.where(ok_e, e_rel, Eloc)                      # Eloc = drop
        sp = jnp.where(ok_e, pp, 0)

        def scatter_one(eg, pg, tg):
            return jnp.zeros((Eloc, C, d), t.dtype).at[eg, pg].set(
                tg, mode="drop")

        buf = jax.vmap(scatter_one)(se, sp, t)                 # (Gl,Eloc,C,d)
        # local FFN: ff-split weights give a PARTIAL d output — the psum
        # below finishes the row-parallel reduction after the K-sum
        h = jax.vmap(lambda pe, xe: mlp_fwd(pe, xe, act))(
            experts_l, buf.swapaxes(0, 1)).swapaxes(0, 1)      # (Gl,Eloc,C,d)

        ok = ok_e & kp
        se2 = jnp.where(ok, e_rel, 0)
        sp2 = jnp.where(ok, pp, 0)

        def combine_one(hx, eg, pg, okg, gg):
            rows = hx[eg, pg]                                  # (TgK, d)
            rows = jnp.where(okg[:, None], rows, 0.0)
            rows = rows * gg[:, None].astype(rows.dtype)
            return jnp.sum(rows.reshape(Tg, K, d), axis=1)     # (Tg, d)

        part = jax.vmap(combine_one)(h, se2, sp2, ok, g)
        if msize > 1:
            part = jax.lax.psum(part, "model")
        return part                                            # (Gl, Tg, d)

    if expert_parallel:
        wspec = {k: P("model") for k in experts}
    else:  # ff dim sharded: (E, d, ff) for up/gate, (E, ff, d) for down
        wspec = {k: (P(None, "model") if k == "w_down"
                     else P(None, None, "model")) for k in experts}
    out = jax.shard_map(
        region,
        in_specs=(wspec, dspec, dspec, dspec, dspec, dspec),
        out_specs=dspec)(experts, safe_e, safe_pos, keep, gates, tok_rep)
    return out.reshape(G * Tg, d)


def moe_fwd(p: dict, cfg: ArchConfig, x: jax.Array,
            act: str = "silu", train: bool = False
            ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    Dispatch is grouped when ``cfg.moe_dispatch_groups > 1``: tokens are
    partitioned into G groups (aligned with the data-parallel shards by the
    sharding constraint below), the position-in-expert cumsum and the
    (E, C, d) scatter run *within* each group, and capacity is per group —
    the standard per-device-capacity semantics of production MoE stacks.
    With G=1 this is one global dispatch.
    """
    if cfg.n_held != cfg.n_experts:
        raise ValueError("the capacity dispatch runs every expert; a "
                         "chip's share of them is served by moe_serve")
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    xt = x.reshape(T, d)
    gate_vals, expert_idx, probs = _route(p, cfg, xt)

    # group count follows the ambient mesh (pod×data shards) so dispatch is
    # per-device on ANY mesh; the config knob covers the no-mesh case
    dax, dsize, msize = _mesh_info()
    G = max(1, cfg.moe_dispatch_groups)
    if dsize > 1 and T % dsize == 0:
        G = dsize
    while G > 1 and T % G:
        G //= 2
    Tg = T // G

    # ---- position-in-expert via per-group cumsum over (Tg*K) assignments
    flat_e = expert_idx.reshape(G, Tg * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (G, TgK, E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot              # pos before self
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=2)[..., 0]

    C = _capacity(Tg, cfg, train)
    keep = pos < C                                             # (G, TgK)
    safe_e = jnp.where(keep, flat_e, E)                        # E => dropped
    safe_pos = jnp.where(keep, pos, 0)

    # ---- shard_map fast path: groups align with the data shards →
    # explicitly-local dispatch (expert- or ff-parallel FFN inside).
    # Token-starved steps (decode: ~8 tokens/group) skip it — there,
    # gathering the tiny token batch against statically-placed weights
    # (the 2D decode layout in launch/sharding.py) beats forcing token
    # locality and re-sharding the weights every step.
    if dsize > 1 and G == dsize and T >= 64 * dsize:
        out = _dispatch_shard_map(p["experts"], cfg, xt, safe_e, safe_pos,
                                  keep, gate_vals, G, Tg, C, act)
        if cfg.n_shared_experts:
            out = out + mlp_fwd(p["shared"], xt, act)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx, E,
                                     dtype=jnp.float32).sum(1), axis=0)
        return out.reshape(B, S, d), E * jnp.sum(me * ce)

    # ---- shard-local scatter into (G, E, C, d) buffers
    tok_rep = jnp.repeat(xt.reshape(G, Tg, d), K, axis=1)      # (G, TgK, d)

    def scatter_group(e, pp, t):
        return jnp.zeros((E, C, d), x.dtype).at[e, pp].set(t, mode="drop")

    buf = jax.vmap(scatter_group)(safe_e, safe_pos, tok_rep)   # (G, E, C, d)
    # groups ride the data axis, experts the model axis (dropped when E
    # doesn't divide — mixtral then runs tensor-parallel experts on ff).
    # NOTE §Perf iter 2 (refuted): forcing a two-step G-sharded→E-sharded
    # reshard here (hoping for one all-to-all) emitted all-to-all AND
    # collective-permute AND kept the all-reduce — 2.5× worse. GSPMD's own
    # propagation from this single constraint is the best layout found.
    buf = constrain(buf, ("pod", "data"), "model")

    # ---- batched expert FFN (xe: (G, C, d) per expert)
    h = jax.vmap(lambda pe, xe: mlp_fwd(pe, xe, act))(
        p["experts"], buf.swapaxes(0, 1))                      # (E, G, C, d)
    h = constrain(h, "model", ("pod", "data"))

    # ---- per-group gather back + gate-combine
    out_rep = jax.vmap(lambda hg, eg, pg: hg[eg % E, pg])(
        h.swapaxes(0, 1), safe_e, safe_pos)                    # (G, TgK, d)
    out_rep = constrain(out_rep, ("pod", "data"))
    out_rep = jnp.where(keep[..., None], out_rep, 0.0)
    out_rep = out_rep * gate_vals.reshape(G, Tg * K, 1).astype(x.dtype)
    out = jnp.sum(out_rep.reshape(T, K, d), axis=1)

    if cfg.n_shared_experts:
        out = out + mlp_fwd(p["shared"], xt, act)

    # ---- Switch-style load-balance aux loss (global)
    me = jnp.mean(probs, axis=0)                               # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx, E, dtype=jnp.float32).sum(1), axis=0)
    aux = E * jnp.sum(me * ce)
    return out.reshape(B, S, d), aux


def moe_fwd_ref(p: dict, cfg: ArchConfig, x: jax.Array,
                act: str = "silu") -> jax.Array:
    """Dense (all-experts) oracle used by tests; no capacity drops."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    gate_vals, expert_idx, _ = _route(p, cfg, xt)
    all_out = jax.vmap(lambda pe: mlp_fwd(pe, xt, act))(p["experts"])  # (E,T,d)
    mask = jax.nn.one_hot(expert_idx, cfg.n_experts)           # (T,K,E)
    combine = jnp.einsum("tke,tk->te", mask, gate_vals)
    out = jnp.einsum("etd,te->td", all_out, combine.astype(x.dtype))
    if cfg.n_shared_experts:
        out = out + mlp_fwd(p["shared"], xt, act)
    return out.reshape(B, S, d)
