"""Paged decode attention's share of its roofline: the live K/V bytes and
operations of the traced interval's decoded tokens at peak, over the
summed device time of the paged_decode_attention kernel, in percent."""
from bench.readers import paged_decode_roofline as read  # noqa: F401
