"""Decode throughput: fused chunked decode vs the per-token baseline.

The fused path (``Model.decode_chunk`` + donated caches) replaces one XLA
dispatch, one full KV-cache copy, and one blocking host sync *per token*
with one dispatch + one transfer *per chunk*. This benchmark measures the
resulting tokens/s on the same engines the container pool runs, at
n ∈ {1, 2, 4} containers — the per-container multiplier the paper's
divide-and-save splits compound on top of.

Emits ``results/decode_throughput.{json,md}`` (human-oriented) and
``results/BENCH_decode.json`` (machine-readable perf trajectory; uploaded
as a CI artifact). ``--smoke`` runs a tiny single-chunk configuration so
CI can keep the benchmark from rotting without paying bench time.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmarks.common import make_requests, save, save_bench, table
from repro.configs.base import reduce_config
from repro.configs.registry import get_config
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.pool import ContainerServingPool


def bench_config(smoke: bool = False):
    """Edge-class serving reduction: decode at this size is
    dispatch/overhead-bound — exactly the regime the fused chunk targets.
    (At pool_scaling's larger d512 reduction this CPU is compute-bound
    per step and the fused win shrinks to noise; both points are real,
    this benchmark tracks the overhead-dominated one.)"""
    if smoke:
        return reduce_config(get_config("qwen3-0.6b"), n_layers=2,
                             d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                             vocab_size=512)
    return reduce_config(get_config("qwen3-0.6b"), n_layers=2, d_model=128,
                         n_heads=4, n_kv_heads=2, d_ff=512,
                         vocab_size=1024)


def measure(model, params, requests, ns=(1, 2, 4), n_slots=2,
            max_len=128, chunk_tokens=None, reps: int = 3) -> list[dict]:
    """Per-token vs chunked tokens/s per container count. Modes are
    interleaved and the best of ``reps`` kept (standard wall-time noise
    filter on a shared host)."""
    rows = []
    for n in ns:
        pools = {}
        for mode, chunked in (("token", False), ("chunk", True)):
            factory = functools.partial(ServingEngine, chunked=chunked,
                                        chunk_tokens=chunk_tokens)
            pools[mode] = ContainerServingPool(
                model, params, n, n_slots_per_container=n_slots,
                max_len=max_len, engine_factory=factory)
            pools[mode].serve_timed(list(requests))       # compile warmup
        best: dict = {m: (np.inf, 0.0, 0) for m in pools}
        for _ in range(reps):
            for mode, pool in pools.items():
                _, per, wall, energy = pool.serve_timed(list(requests))
                toks = sum(r.n_tokens for r in per)
                if wall < best[mode][0]:
                    best[mode] = (wall, energy, toks)
        (w_tok, e_tok, t_tok), (w_chk, e_chk, t_chk) = (best["token"],
                                                        best["chunk"])
        rows.append({
            "n": n,
            "wall_token_s": w_tok, "wall_chunk_s": w_chk,
            "tokens": t_chk,
            "tps_token": t_tok / w_tok, "tps_chunk": t_chk / w_chk,
            "speedup": (t_chk / w_chk) / (t_tok / w_tok),
            "energy_token_j": e_tok, "energy_chunk_j": e_chk,
        })
    return rows


def run(quick: bool = False, smoke: bool = False) -> str:
    import jax

    # budgets are chunk-aligned (max_new - 1 lands on a power-of-two
    # chunk length) so the steady state is one fused dispatch per slot
    # generation — the deployment fast path the README documents
    if smoke:
        ns, n_requests, max_new, reps, chunk = (1,), 2, 5, 1, 4
    elif quick:
        ns, n_requests, max_new, reps, chunk = (1, 2), 8, 33, 3, None
    else:
        ns, n_requests, max_new, reps, chunk = (1, 2, 4), 16, 33, 5, None
    cfg = bench_config(smoke)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    requests = make_requests(cfg, n_requests, max_new)

    rows = measure(model, params, requests, ns=ns, chunk_tokens=chunk,
                   reps=reps)
    payload = {"measured": rows, "config": cfg.name, "smoke": smoke,
               "n_requests": n_requests, "max_new_tokens": max_new}
    md_rows = [[r["n"], r["wall_token_s"], r["wall_chunk_s"],
                r["tps_token"], r["tps_chunk"], r["speedup"],
                r["energy_token_j"], r["energy_chunk_j"]] for r in rows]
    lines = ["# Decode throughput — fused chunked decode vs per-token",
             "", f"{n_requests} requests × {max_new} new tokens, "
             f"arch {cfg.name} (bench reduction)", ""]
    lines += table(["n", "token wall (s)", "chunk wall (s)", "tok/s token",
                    "tok/s chunk", "speedup", "E token (J)", "E chunk (J)"],
                   md_rows)
    n1 = rows[0]
    lines += ["", f"n=1 chunked speedup: {n1['speedup']:.2f}× "
              f"({n1['tps_token']:.1f} → {n1['tps_chunk']:.1f} tokens/s)"]
    save_bench("decode", {
        "config": cfg.name, "smoke": smoke,
        "n1_tokens_per_s_token": n1["tps_token"],
        "n1_tokens_per_s_chunk": n1["tps_chunk"],
        "n1_speedup": n1["speedup"],
        "per_n": {str(r["n"]): {"tokens_per_s_chunk": r["tps_chunk"],
                                "tokens_per_s_token": r["tps_token"],
                                "wall_s": r["wall_chunk_s"],
                                "energy_j": r["energy_chunk_j"]}
                  for r in rows}})
    return save("decode_throughput", payload, lines)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config, one chunk — CI rot check only")
    args = ap.parse_args()
    print(run(quick=args.quick, smoke=args.smoke))
