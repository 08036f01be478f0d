"""Chip smoke test: the main serving path on a TPU, end to end.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the n-sweep on a four-chip host

One chip: full-width qwen3-0.6b (28 layers, d_model 1024, vocab 151936;
float32 weights drawn from ``--seed``, nothing downloaded) served through
the entry points a user calls: Router -> ThreadBackend -> ServingEngine
-> KV cache -> Pallas kernels. Eight requests with ragged prompts in two
prompt buckets are served at n=1 (a cold pass, then a warm one) and n=2
containers on the dense cache, then at n=1 on the paged cache. The run
fails on a request that did not complete its full token budget, on any
retry, failed or rejected request, and on any container failure (whose
traceback it prints). It checks that n=1 and n=2 give identical greedy
tokens, that the served programs contain Pallas calls, and that each
Pallas kernel the path ran agrees with its jnp oracle at the served
shapes. The times it prints are set-up and sanity figures (compilation
included where it says so), not benchmark metrics.

``--four-chips`` runs only the paper's split on a v5e host: n=4
one-chip replicas (SubmeshBackend) against n=1, one replica placed over
all four chips. It checks identical greedy tokens and that each
container's params and cache lie on its own chips only.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache`` in the checkout. Without a TPU the script exits non-zero
and prints no result. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen3-0.6b"
N_REQUESTS = 8
MAX_NEW = 24
# ragged prompt lengths: the first half in bucket 16, the second in 128
PROMPT_LENS = ((9, 16), (65, 128))
N_SLOTS = 2                    # the launcher's default slots per container
# largest |kernel - oracle| admitted in float32, the oracle run at
# "highest" matmul precision. A Pallas TPU kernel contracts float32
# operands in one bfloat16 pass (Mosaic's default, as XLA's DEFAULT
# precision is on the TPU), rounding each operand to 8 significant bits;
# over unit-normal attention inputs that errs by about 1e-2. A faulty
# mask, block or index errs by tenths or more.
F32_TOL = 3e-2


def make_requests(vocab: int, seed: int, lens=PROMPT_LENS):
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(N_REQUESTS):
        lo, hi = lens[i * len(lens) // N_REQUESTS]
        n = int(rng.integers(lo, hi + 1))
        out.append(Request(rid=i, prompt=rng.integers(0, vocab, (n,),
                                                      dtype=np.int32),
                           max_new_tokens=MAX_NEW))
    return out


def serve_pass(router, requests):
    """Submit every request, consume every stream. Returns per-request
    tokens, seconds to the first completion, wall seconds and problems."""
    from repro.serving import RequestFailed, RetryEvent
    t0 = time.perf_counter()
    handles = [router.submit(r) for r in requests]
    tokens, problems = [], []
    for r, h in zip(requests, handles):
        try:
            for ev in h.stream():
                if isinstance(ev, RetryEvent):
                    problems.append(f"request {r.rid} retried: {ev.reason}")
        except RequestFailed as e:      # failed or rejected
            problems.append(str(e))
            tokens.append(None)
            continue
        toks = list(h.completion.tokens)
        if len(toks) != r.max_new_tokens:
            problems.append(f"request {r.rid}: {len(toks)} of "
                            f"{r.max_new_tokens} tokens")
        tokens.append(toks)
    wall = time.perf_counter() - t0
    done = [h.done_at for h in handles if h.done_at is not None]
    first = min(done) - t0 if done else float("nan")
    return tokens, first, wall, problems


def serve(label, backend, requests, problems, passes=1):
    """Serve ``requests`` ``passes`` times through one Router over
    ``backend``; append every problem to ``problems``. Returns the last
    pass's tokens."""
    from repro.serving import Router
    tokens = None
    with Router(backend) as router:
        for p in range(passes):
            reqs = [dataclasses.replace(r, rid=r.rid + 1000 * p)
                    for r in requests]
            tokens, first, wall, probs = serve_pass(router, reqs)
            n_ok = sum(t is not None and len(t) == MAX_NEW for t in tokens)
            kind = "first, any compile included" if p == 0 else "warm"
            print(f"[{label}] pass {p} ({kind}): {n_ok}/{len(reqs)} "
                  f"requests complete with {MAX_NEW} tokens; first "
                  f"completion {first:.3f}s, all {wall:.3f}s")
            problems += [f"{label}: {m}" for m in probs]
        fails = list(backend.failures)
    if fails:
        problems.append(f"{label}: {len(fails)} container failure(s); the "
                        f"first:\n{fails[0].message}")
    return tokens


def compare_tokens(label, a, b, problems, required=True):
    same = sum(x is not None and x == y for x, y in zip(a, b))
    print(f"[{label}] greedy tokens identical for {same}/{len(a)} requests")
    if required and same != len(a):
        problems.append(f"{label}: greedy tokens differ")


def pallas_calls(fn, *args) -> int:
    import jax
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def check_served_programs(model, params, dense_eng, paged_eng, problems):
    """The served prefill and decode programs must contain Pallas calls:
    no jnp oracle stands in for a kernel on the chip."""
    import jax.numpy as jnp
    tok = jnp.zeros((N_SLOTS, 16), jnp.int32)
    counts = {
        "prefill": pallas_calls(
            lambda p, b: model.prefill(p, b, model.init_cache(N_SLOTS, 512)),
            params, {"tokens": tok}),
        "decode (dense)": pallas_calls(
            model.decode_step, params, tok[:, :1], dense_eng.cache,
            jnp.zeros((N_SLOTS,), jnp.int32)),
        "decode (paged)": pallas_calls(
            model.decode_step, params,
            jnp.zeros((len(paged_eng.slots), 1), jnp.int32), paged_eng.cache,
            jnp.zeros((len(paged_eng.slots),), jnp.int32)),
    }
    for name, n in counts.items():
        print(f"[programs] {name}: {n} Pallas call(s) in the lowered program")
        if n == 0:
            problems.append(f"served {name} program has no Pallas call")


def check_kernels(cfg, dense_eng, paged_eng, seed, problems):
    """Each Pallas kernel the served path ran, called once more at the
    served shapes against its jnp oracle run at highest precision. The
    same oracle run by XLA at default precision is printed beside it: a
    kernel should err about as much as XLA's own matmuls do."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    H, Hkv, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    cases = []
    for S in (16, 128):             # the prompt buckets served
        q, k, v = normal(N_SLOTS, S, H, K), normal(N_SLOTS, S, Hkv, K), \
            normal(N_SLOTS, S, Hkv, K)
        cases.append((f"flash_attention (prefill, B={N_SLOTS}, S={S})",
                      lambda q=q, k=k, v=v: ops.flash_attention(q, k, v),
                      lambda q=q, k=k, v=v: ref.flash_attention(q, k, v)))

    B, W = dense_eng.cache["stack"]["k"].shape[1:3]
    q, k, v = normal(B, H, K), normal(B, W, Hkv, K), normal(B, W, Hkv, K)
    lengths = jax.random.randint(next(keys), (B,), 1, W + 1)
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    cases.append((f"decode_attention (dense, B={B}, W={W})",
                  lambda: ops.decode_attention(q, k, v, valid),
                  lambda: ref.decode_attention(q, k, v, valid)))

    P, bs = paged_eng.cache["stack"]["k_pages"].shape[1:3]
    B, nblk = paged_eng.cache["stack"]["table"].shape[1:3]
    qp, kp, vp = (normal(B, H, K), normal(P, bs, Hkv * K),
                  normal(P, bs, Hkv * K))
    table = jax.random.randint(next(keys), (B, nblk), 0, P)
    plens = jax.random.randint(next(keys), (B,), 1, nblk * bs + 1)
    cases.append((f"paged_decode_attention (B={B}, pages={P}, "
                  f"block_size={bs})",
                  lambda: ops.paged_decode_attention(qp, kp, vp, table,
                                                     plens),
                  lambda: ref.paged_decode_attention(qp, kp, vp, table,
                                                     plens)))
    for name, kernel, oracle in cases:
        got = kernel()
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = float(jnp.max(jnp.abs(got - want)))
        err_xla = float(jnp.max(jnp.abs(oracle() - want)))
        ok = err <= F32_TOL and bool(jnp.all(jnp.isfinite(got)))
        print(f"[kernels] {name}: max |kernel - oracle| = {err:.3e} "
              f"(tolerance {F32_TOL:g}; XLA at default precision "
              f"{err_xla:.3e}) {'ok' if ok else 'FAIL'}")
        if not ok:
            problems.append(f"{name}: error {err:.3e} past {F32_TOL:g}")


def one_chip(model, params, requests, seed, problems) -> None:
    from repro.serving import EngineConfig
    from repro.serving.backend import ThreadBackend
    dense = EngineConfig(n_slots=N_SLOTS)
    b1 = ThreadBackend(model, params, 1, config=dense)
    dense_eng = b1.engines[0]
    tok1 = serve("dense n=1", b1, requests, problems, passes=2)
    tok2 = serve("dense n=2", ThreadBackend(model, params, 2, config=dense),
                 requests, problems)
    compare_tokens("dense n=1 vs n=2", tok1, tok2, problems)
    bp = ThreadBackend(model, params, 1,
                       config=EngineConfig(n_slots=N_SLOTS, cache="paged"))
    paged_eng = bp.engines[0]
    tokp = serve("paged n=1", bp, requests, problems)
    # the dense and paged decode kernels reduce in different orders, so
    # a near-tie may flip an argmax: reported, not required
    compare_tokens("dense vs paged n=1", tok1, tokp, problems,
                   required=False)
    check_served_programs(model, params, dense_eng, paged_eng, problems)
    check_kernels(model.cfg, dense_eng, paged_eng, seed, problems)


def four_chips(model, params, requests, problems) -> None:
    import jax

    from repro.launch.mesh import make_container_meshes
    from repro.launch.sharding import tree_device_set
    from repro.serving import EngineConfig
    from repro.serving.backend import SubmeshBackend
    if len(jax.devices()) < 4:
        problems.append(f"--four-chips needs 4 devices, JAX found "
                        f"{len(jax.devices())}")
        return
    tokens = {}
    for n in (4, 1):
        meshes = make_container_meshes(4, n)
        backend = SubmeshBackend(model, params, n, meshes=meshes,
                                 config=EngineConfig(n_slots=N_SLOTS))
        sets = []
        for cid, eng in enumerate(backend.engines):
            want = frozenset(meshes[cid].devices.flat)
            on_p, on_c = tree_device_set(eng.params), tree_device_set(
                eng.cache)
            print(f"[n={n}] container {cid}: mesh devices "
                  f"{sorted(d.id for d in want)}, params on "
                  f"{sorted(d.id for d in on_p)}, cache on "
                  f"{sorted(d.id for d in on_c)}")
            if on_p != want or on_c != want:
                problems.append(f"n={n} container {cid} is not confined to "
                                "its own chips")
            sets.append(want)
        if sum(map(len, sets)) != len(frozenset().union(*sets)):
            problems.append(f"n={n}: containers share chips")
        tokens[n] = serve(f"submesh n={n}", backend, requests, problems)
    compare_tokens("submesh n=4 vs n=1", tokens[4], tokens[1], problems)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the n=4 one-chip replicas vs n=1 over "
                         "four chips comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    print(f"compile cache: {use_compile_cache()}")
    import jax

    from repro.core.roofline import check_device_kind
    from repro.kernels import ops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if ops._mode() != "tpu":
        print(f"kernels would run in {ops._mode()!r} mode, not as Pallas "
              "TPU kernels", file=sys.stderr)
        return 1
    kind = dev.device_kind
    print(f"device: {dev.platform} {kind!r} x{len(jax.devices())}")
    try:
        check_device_kind(kind)
    except ValueError as e:
        print(e, file=sys.stderr)
        return 1

    from repro.configs.registry import get_config
    from repro.models.model import Model
    cfg = get_config(ARCH)
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {n_params:,} float32 params, "
          f"built in {time.perf_counter() - t0:.1f}s")
    requests = make_requests(cfg.vocab_size, args.seed)
    print(f"requests: {N_REQUESTS}, prompt lengths "
          f"{[len(r.prompt) for r in requests]}, max_new_tokens {MAX_NEW}")

    problems: list[str] = []
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(model, params, requests, problems)
    else:
        one_chip(model, params, requests, args.seed, problems)
    print(f"phases took {time.perf_counter() - t0:.1f}s")
    if problems:
        print(f"FAILED ({len(problems)} problem(s)):", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
