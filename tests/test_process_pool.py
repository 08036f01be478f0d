"""Process-per-container pool: the paper's real isolation mechanism.

Parity harness for serving/process_pool.py — greedy completions from
pinned child processes must bit-match the in-process single-engine
baseline (params rebuilt from seed in one lane, handed off via .npz in
the other), per-container core sets must be pairwise disjoint, and warm
children must survive across waves. Spawn+compile makes these seconds-
scale, so the expensive ones are marked ``slow`` (the CI fast lane skips
them; the dedicated process-pool CI job runs this module in full).
"""
from __future__ import annotations

import os

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.model import Model
from repro.serving import (AdaptiveServingPool, ProcessContainerPool,
                           Request, ServingEngine, share_params)
from repro.serving.backend import ProcessBackend
from repro.serving.process_pool import save_params

HOST_CORES = len(os.sched_getaffinity(0))


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("qwen3-0.6b-reduced")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _requests(cfg, n, plen=6, max_new=3, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (plen,),
                                        dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def test_too_many_containers_fails_fast_without_spawn(small_lm):
    """More containers than cores cannot be pairwise disjoint: the pool
    must refuse at construction, before paying any spawn cost."""
    model, _ = small_lm
    with pytest.raises(ValueError, match="disjoint"):
        ProcessContainerPool(model.cfg, HOST_CORES + 1)


def test_shared_cores_need_explicit_opt_in(small_lm):
    model, _ = small_lm
    pool = ProcessContainerPool(model.cfg, HOST_CORES + 1,
                                allow_shared_cores=True)
    assert len(pool.core_sets) == HOST_CORES + 1
    # round-robin singletons: every assigned core is a real host core
    assert set().union(*pool.core_sets) <= set(os.sched_getaffinity(0))


@pytest.mark.slow
def test_process_pool_parity_disjoint_cores_and_warm_reuse(small_lm,
                                                           tmp_path):
    """The acceptance harness: for n ∈ {1, 2}, greedy completions from
    pinned child processes bit-match the single-engine baseline (n=1
    rebuilds params from the seed, n=2 loads the parent's params from the
    .npz handoff), children report pairwise-disjoint core affinities, and
    a second wave reuses the warm children (same results, no respawn)."""
    model, params = small_lm
    cfg = model.cfg
    reqs = _requests(cfg, 5)

    eng = ServingEngine(model, params, n_slots=2, max_len=64)
    eng.submit_many(list(reqs))
    want = {c.rid: (tuple(c.tokens), c.prompt_len) for c in eng.run()}

    handoff = save_params(params, str(tmp_path / "params.npz"))
    for n, params_path in ((1, None), (2, handoff)):
        if n > HOST_CORES:
            pytest.skip(f"needs {n} cores, host exposes {HOST_CORES}")
        with ProcessContainerPool(cfg, n, n_slots_per_container=2,
                                  max_len=64, params_seed=0,
                                  params_path=params_path) as pool:
            ordered, per, wall, energy = pool.serve_timed(list(reqs))
            got = {c.rid: (tuple(c.tokens), c.prompt_len) for c in ordered}
            assert got == want, f"n={n} diverged from the baseline"
            assert [c.rid for c in ordered] == [r.rid for r in reqs]
            assert wall > 0 and energy > 0
            assert len(per) == n
            assert sum(r.n_requests for r in per) == len(reqs)
            for r in per:
                assert r.busy_s > 0 and r.energy_j > 0

            sets = pool.reported_core_sets
            assert sets is not None and len(sets) == n
            # children measured their OWN affinity after jax init: it must
            # be exactly the parent's assignment, pairwise disjoint
            assert sets == list(pool.core_sets)
            for i, a in enumerate(sets):
                for b in sets[i + 1:]:
                    assert not (a & b), "containers share cores"

            workers = pool._workers
            again, _, _, _ = pool.serve_timed(list(reqs))
            assert {c.rid: (tuple(c.tokens), c.prompt_len)
                    for c in again} == want
            assert pool._workers is workers    # warm: no respawn


@pytest.mark.slow
def test_adaptive_pool_process_isolation_converges_warm(small_lm):
    """AdaptiveServingPool(isolation='process'): waves are served by warm
    per-count process pools (spawn paid once per count), results stay
    order-correct, and close() shuts every child down."""
    model, params = small_lm
    counts = [1, 2] if HOST_CORES >= 2 else [1]
    apool = AdaptiveServingPool(model, params, counts, objective="energy",
                                n_slots_per_container=2, max_len=64,
                                isolation="process", params_seed=0)
    try:
        for wave in range(3):
            reqs = _requests(model.cfg, 4, seed=wave)
            out = apool.serve_wave(reqs)
            assert [c.rid for c in out] == [r.rid for r in reqs]
        assert apool.scheduler.n_observations == 3
        # converged serving reuses cached pools: at most one per count
        assert set(apool._pools) <= set(counts)
        procs = [proc for pool in apool._pools.values()
                 for (proc, _) in (pool._workers or [])]
        assert procs
    finally:
        apool.close()
    assert all(not p.is_alive() for p in procs)
    assert apool._pools == {}


def test_shared_memory_params_roundtrip_in_process(small_lm):
    """``share_params`` lays the leaves out in one shared-memory segment
    and the child-side loader rebuilds a byte-identical tree — verified
    in-process (no spawn cost), including the dangling-alias hazard: the
    rebuilt leaves must survive the segment being closed and unlinked."""
    from repro.serving.backend import _load_params_shm

    model, params = small_lm
    with share_params(params) as share:
        rebuilt = _load_params_shm(model, share.handle)
    # the share is now closed AND unlinked; the copies must be intact
    want = jax.tree_util.tree_leaves(params)
    got = jax.tree_util.tree_leaves(rebuilt)
    assert len(want) == len(got)
    for a, b in zip(want, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shared_params_handle_is_picklable(small_lm):
    import pickle
    _, params = small_lm
    with share_params(params) as share:
        handle = pickle.loads(pickle.dumps(share.handle))
        assert handle == share.handle


def test_params_path_and_shm_are_mutually_exclusive(small_lm):
    model, _ = small_lm
    with pytest.raises(ValueError, match="not both"):
        ProcessContainerPool(model.cfg, 1, params_path="x.npz",
                             params_shm=object())


@pytest.mark.slow
def test_shared_memory_handoff_parity_with_npz(small_lm, tmp_path):
    """The shared-memory params handoff must serve bit-identical
    completions to the ``.npz`` handoff (both carry the parent's exact
    float bytes) — the ROADMAP's cross-process shared-memory leftover."""
    model, params = small_lm
    cfg = model.cfg
    reqs = _requests(cfg, 4)

    handoff = save_params(params, str(tmp_path / "params.npz"))
    with ProcessContainerPool(cfg, 1, n_slots_per_container=2,
                              max_len=64, params_path=handoff) as pool:
        via_npz, _, _, _ = pool.serve_timed(list(reqs))

    with share_params(params) as share:
        with ProcessContainerPool(cfg, 1, n_slots_per_container=2,
                                  max_len=64,
                                  params_shm=share.handle) as pool:
            via_shm, _, _, _ = pool.serve_timed(list(reqs))
            # warm second wave over the mapped params
            again, _, _, _ = pool.serve_timed(list(reqs))
    key = lambda comps: {c.rid: (tuple(c.tokens), c.prompt_len)  # noqa: E731
                         for c in comps}
    assert key(via_shm) == key(via_npz)
    assert key(again) == key(via_npz)


def test_process_isolation_rejects_counts_past_core_budget():
    """Fail fast at construction (mirrors the submesh divisor check): a
    feasible count beyond the core budget would otherwise crash the first
    time the scheduler probes it."""
    from repro.serving import synthetic_pool_factory
    with pytest.raises(ValueError, match="core budget"):
        AdaptiveServingPool(None, None, [1, HOST_CORES + 1],
                            pool_factory=synthetic_pool_factory(
                                lambda n: 1.0 / n),
                            isolation="process")


def test_process_isolation_incompatible_with_submesh():
    from repro.serving import synthetic_pool_factory
    with pytest.raises(ValueError, match="submesh placement"):
        AdaptiveServingPool(None, None, [1, 2],
                            pool_factory=synthetic_pool_factory(
                                lambda n: 1.0 / n),
                            isolation="process", submesh_devices=8)


def test_process_backend_refuses_a_tpu_parent(small_lm, monkeypatch):
    """A TPU belongs to one process: a parent that holds it cannot spawn
    children that need it, so the backend refuses before any spawn."""
    model, _ = small_lm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="needs a CPU parent"):
        ProcessBackend(model.cfg, 1)
