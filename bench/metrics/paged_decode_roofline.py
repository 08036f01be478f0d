"""Paged decode attention's share of its roofline: the live K/V bytes and
operations of the traced interval's decoded tokens at peak, over the
summed device time of the paged_decode_attention kernel, in percent."""
from bench.readers import decode_roofline


def read(ctx):
    return decode_roofline(ctx, r"paged_decode_attention",
                           ctx.work.decode_attention_work)
