"""Latent decode attention's share of its roofline: the live latent
bytes (kv_lora_rank + rope dims per key and layer) and operations of the
traced interval's decoded tokens at peak, over the summed device time of
the mla_decode_ctx kernel, in percent."""
from bench.readers import decode_roofline


def read(ctx):
    return decode_roofline(ctx, r"mla_decode_ctx",
                           ctx.work.decode_attention_work)
