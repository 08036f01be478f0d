"""MoE dispatch: dropless-capacity equivalence with the dense oracle,
capacity-drop semantics, aux-loss behaviour."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import moe as moe_lib


def _cfg(**over):
    base = get_config("mixtral-8x22b-reduced")
    return dataclasses.replace(base, **over) if over else base


def test_dropless_matches_dense_oracle():
    cfg = _cfg()          # reduced config sets eval_cf = E/K (dropless)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    got, _ = moe_lib.moe_fwd(p, cfg, x, train=False)
    want = moe_lib.moe_fwd_ref(p, cfg, x)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_shared_experts_added():
    cfg = _cfg(n_shared_experts=1)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    got, _ = moe_lib.moe_fwd(p, cfg, x)
    want = moe_lib.moe_fwd_ref(p, cfg, x)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_tiny_capacity_drops_tokens():
    """With capacity factor ~0 every token drops and the output is ~zero
    (plus shared experts if any — none here). One global dispatch group so
    the per-group capacity floor (4 rows) doesn't mask the drops."""
    cfg = _cfg(moe_eval_cf=1e-9, moe_dispatch_groups=1)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    got, _ = moe_lib.moe_fwd(p, cfg, x)
    # capacity floor is 4 rows/expert, so a few tokens survive; most drop
    frac_zero = float(jnp.mean(jnp.all(got == 0.0, axis=-1)))
    assert frac_zero > 0.5


def test_aux_loss_balanced_vs_collapsed():
    """A uniform router must score (near-)minimal aux loss; a collapsed
    router (all tokens to one expert) must score ~E times that."""
    cfg = _cfg()
    E = cfg.n_experts
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model))

    p_uniform = dict(p, router=jnp.zeros_like(p["router"]))
    _, aux_u = moe_lib.moe_fwd(p_uniform, cfg, x, train=True)

    # collapse: positive inputs + a one-column router → every token routes
    # its top-1 to expert 0 with probability ~1
    x_pos = jnp.abs(x) + 0.5
    collapsed = jnp.zeros_like(p["router"]).at[:, 0].set(100.0)
    p_col = dict(p, router=collapsed)
    _, aux_c = moe_lib.moe_fwd(p_col, cfg, x_pos, train=True)
    # Switch aux: uniform = K exactly; collapsed = E (me0=ce0=1) — the
    # E=4, K=2 reduced config gives a clean 2× separation
    assert float(aux_c) > 1.5 * float(aux_u)


def test_moe_grads_flow_to_experts():
    cfg = _cfg()
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))

    def loss(p):
        out, aux = moe_lib.moe_fwd(p, cfg, x, train=True)
        return jnp.sum(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    gnorm_experts = sum(float(jnp.sum(jnp.abs(l)))
                        for l in jax.tree.leaves(g["experts"]))
    gnorm_router = float(jnp.sum(jnp.abs(g["router"])))
    assert gnorm_experts > 0
    assert gnorm_router > 0


# ---------------------------------------------------------------------------
# the serving programs' dropless dispatch over the held experts
# ---------------------------------------------------------------------------
def _held_oracle(p, cfg, x):
    """Every routed assignment to a held expert, computed densely: the
    held experts' part of the routed output plus the shared experts."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    gate, idx, _ = moe_lib._route(p, cfg, xt)
    local = idx - cfg.first_expert
    out = jnp.zeros_like(xt)
    for e in range(cfg.n_held):
        pe = jax.tree.map(lambda a: a[e], p["experts"])
        w = jnp.sum(jnp.where(local == e, gate, 0.0), axis=-1)
        out = out + w[:, None] * moe_lib.mlp_fwd(pe, xt)
    if cfg.n_shared_experts:
        out = out + moe_lib.mlp_fwd(p["shared"], xt)
    return out.reshape(B, S, d)


@pytest.mark.parametrize("tile", [8, 128])
@pytest.mark.parametrize("batch,seq", [(1, 1), (3, 1), (2, 16), (4, 64),
                                       (256, 1)])
def test_serve_is_dropless_at_any_batch(monkeypatch, tile, batch, seq):
    """The full share: every assignment computed, at one token or many,
    with one row tile per expert or many; the counts say so."""
    monkeypatch.setattr(moe_lib, "TILE_ROWS", tile)
    cfg = _cfg(n_shared_experts=1)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, cfg.d_model))
    got, counts = moe_lib.moe_serve(p, cfg, x)
    np.testing.assert_allclose(got, moe_lib.moe_fwd_ref(p, cfg, x),
                               atol=1e-5, rtol=1e-5)
    assert counts.shape == (batch * seq, 2)
    assert np.all(np.asarray(counts[:, 0]) == cfg.n_experts_per_tok)
    assert np.all(np.asarray(counts[:, 1]) == 0)


def test_serve_drops_nothing_when_every_token_picks_the_same_experts(
        monkeypatch):
    """A collapsed router sends all 512 tokens' assignments to the held
    experts: far past any capacity, and nothing dropped."""
    monkeypatch.setattr(moe_lib, "TILE_ROWS", 16)
    cfg = _cfg(moe_eval_cf=1e-9)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, :2].set(100.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                  (8, 64, cfg.d_model))) + 0.5
    got, counts = moe_lib.moe_serve(p, cfg, x)
    np.testing.assert_allclose(got, moe_lib.moe_fwd_ref(p, cfg, x),
                               atol=1e-5, rtol=1e-5)
    assert int(counts[:, 0].sum()) == 512 * cfg.n_experts_per_tok
    assert int(counts[:, 1].sum()) == 0


@pytest.mark.parametrize("first", [0, 3, 6])
def test_serve_computes_the_held_share(monkeypatch, first):
    """Two experts held of eight, from ``first``: the router scores all
    eight, and the output is the held experts' part plus the shared
    experts; the counts are the assignments that fell on the share."""
    monkeypatch.setattr(moe_lib, "TILE_ROWS", 8)
    cfg = _cfg(n_experts=8, n_experts_per_tok=3, experts_held=2,
               first_expert=first, n_shared_experts=1)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert p["router"].shape[-1] == 8
    assert p["experts"]["w_up"].shape[0] == 2
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 20, cfg.d_model))
    got, counts = moe_lib.moe_serve(p, cfg, x)
    np.testing.assert_allclose(got, _held_oracle(p, cfg, x), atol=1e-5,
                               rtol=1e-5)
    _, idx, _ = moe_lib._route(p, cfg, x.reshape(-1, cfg.d_model))
    held = (idx >= first) & (idx < first + 2)
    np.testing.assert_array_equal(counts[:, 0], held.sum(-1))
    assert 0 < int(held.sum()) < held.size


def test_the_capacity_dispatch_refuses_a_partial_share():
    cfg = _cfg(n_experts=8, experts_held=2)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.ones((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="share"):
        moe_lib.moe_fwd(p, cfg, x)


def test_gates_are_renormalised_only_when_asked():
    cfg = _cfg(n_experts_per_tok=2)
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    xt = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    norm, _, _ = moe_lib._route(p, cfg, xt)
    raw, idx, probs = moe_lib._route(
        p, dataclasses.replace(cfg, norm_topk_prob=False), xt)
    np.testing.assert_allclose(norm.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(raw, jnp.take_along_axis(probs, idx, -1))
    assert float(raw.sum(-1).max()) < 1.0


def test_the_grouped_kernel_matches_its_oracle():
    """The Pallas grouped matmul in interpret mode: tiles of several
    experts read at a layer of the stack, tiles past ``n_tiles``
    skipped."""
    from repro.kernels import moe_gmm, ref
    L, E, d, ff, tm, nt = 2, 3, 128, 256, 8, 5
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    xs = jax.random.normal(k[0], (nt * tm, d))
    wg, wu = (jax.random.normal(kk, (L, E, d, ff)) / 10 for kk in k[1:3])
    wd = jax.random.normal(k[3], (L, E, ff, d)) / 10
    te = jnp.array([0, 0, 2, 1, 1], jnp.int32)
    for n in (3, 5):
        got = moe_gmm.moe_expert_gmm(xs, te, jnp.int32(n), jnp.int32(1),
                                     wg, wu, wd, interpret=True)
        want = ref.moe_expert_gmm(xs, te, jnp.int32(n), 1, wg, wu, wd)
        np.testing.assert_allclose(got[:n * tm], want[:n * tm], atol=1e-4,
                                   rtol=1e-4)
