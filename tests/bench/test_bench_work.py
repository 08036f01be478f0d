"""Work counted from the request log, and the per-layer arithmetic,
through the dense family's work model."""
from __future__ import annotations

import pytest

from bench import readers, spec, work
from bench.drive import Log, Record
from bench.trace import Event, Reduced

N = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "hd": 4, "ff": 16, "V": 32}
W = work.DenseWork(N)


def test_matmul_params_and_token_flops():
    per_layer = 8 * 16 + 2 * 8 * 8 + 16 * 8 + 3 * 8 * 16
    assert work.matmul_params(N) == 2 * per_layer + 8 * 32
    assert work.token_flops(N, 5) == (2 * work.matmul_params(N)
                                      + 4 * 2 * 4 * 4 * 5)


def test_prefill_is_the_sum_of_its_tokens():
    s = 7
    assert work.prefill_flops(N, s) == sum(work.token_flops(N, i + 1)
                                           for i in range(s))


def test_decode_work_counts_live_keys_only():
    flops, data = work.decode_attention_work(N, keys=10, kv_bytes=2)
    assert flops == 4 * 2 * 4 * 4 * 10
    assert data == 2 * (2 * 10 * 2 * 4 * 2 + 2 * 4 * 4 * 2)


def _ctx(kernel_ns, token_s, prompt_len=4):
    rec = Record(1, 0.0, prompt_len, 8, token_s=token_s,
                 tokens=list(range(len(token_s))))
    log = Log(records={1: rec}, window_s=10.0)
    log.trace_span = (1.0, 2.0)
    ops = [Event("/device:TPU:0", "XLA Ops",
                 "%paged_decode_attention.3 = bf16[1] custom-call()",
                 1_000_000_000, kernel_ns),
           Event("/device:TPU:0", "XLA Ops", "%fusion.1 = bf16[1] fusion()",
                 1_500_000_000, 100_000_000)]
    trace = Reduced(1_000_000_000, 2_000_000_000, {"/device:TPU:0": ops}, [])
    return readers.Context(log=log, trace=trace, work=W,
                           peaks={"bf16_flops": 1e9,
                                  "hbm_bytes_per_s": 1e9},
                           chips=1, kv_bytes=2, act_bytes=2)


def test_roofline_share_from_served_tokens_and_kernel_time():
    # tokens 1..3 decoded inside the traced interval at 4+i keys
    ctx = _ctx(kernel_ns=1_000_000, token_s=[0.5, 1.2, 1.4, 1.6, 2.5])
    flops = data = 0
    for i in (1, 2, 3):
        f, b = work.decode_attention_work(N, 4 + i, 2)
        flops, data = flops + f, data + b
    least = max(flops / 1e9, data / 1e9)
    read = spec.metric_reader("paged_decode_roofline")
    assert read(ctx) == pytest.approx(100 * least / 1e-3)


def test_the_roofline_readers_count_with_the_work_they_are_given():
    """A family's kernel, such as a latent-attention decode, gets its
    roofline from its own work function: here one operation per key and
    one byte per element of a stated size."""
    ctx = _ctx(kernel_ns=1_000_000, token_s=[1.1, 1.2, 1.4, 2.5])
    calls = []

    def per_key(n, nbytes):
        calls.append((n, nbytes))
        return n, n * nbytes

    got = readers.decode_roofline(ctx, "paged_decode_attention", per_key)
    assert calls == [(5, 2), (6, 2)]
    assert got == pytest.approx(100 * (22 / 1e9) / 1e-3)
    calls.clear()
    # the prefill reader takes the first token of each request alone,
    # with the activations' bytes, and is silent without its kernel
    assert readers.prefill_roofline(ctx, "flash_attention", per_key) is None
    assert calls == [(4, 2)]
    got = readers.prefill_roofline(ctx, "paged_decode_attention", per_key)
    assert got == pytest.approx(100 * (8 / 1e9) / 1e-3)


def test_roofline_is_silent_without_its_kernel():
    ctx = _ctx(kernel_ns=1_000_000, token_s=[0.5, 1.2])
    assert spec.metric_reader("flash_prefill_roofline")(ctx) is None


def test_mfu_and_idle_share():
    ctx = _ctx(kernel_ns=200_000_000, token_s=[1.1, 1.2])
    busy = 0.3
    flops = work.prefill_flops(N, 4) + work.token_flops(N, 5)
    assert readers.mfu(ctx) == pytest.approx(100 * flops / (busy * 1e9))
    assert readers.idle_share(ctx) == pytest.approx(70.0)
