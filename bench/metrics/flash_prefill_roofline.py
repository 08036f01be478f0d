"""Flash prefill attention's share of its roofline: the causal
operations and q/k/v/o bytes of the traced interval's prefills at peak,
over the summed device time of the flash_attention kernel, in percent."""
from bench.readers import prefill_roofline


def read(ctx):
    return prefill_roofline(ctx, r"flash_attention",
                            ctx.work.prefill_attention_work)
