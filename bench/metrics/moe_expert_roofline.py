"""The held experts' grouped matmul's share of its roofline: the
operations of the traced interval's tokens' expected held assignments
(prefilled and decoded), and one layer's held experts' weight bytes for
each call in the trace, at peak, over the summed device time of the
moe_expert_gmm kernel, in percent. None where the kernel did not run or
the family has no held experts."""
from bench.readers import roofline
from bench.trace import op_kind, op_name

KERNEL = "moe_expert_gmm"


def read(ctx):
    flops_per_token = getattr(ctx.work, "expert_token_flops", None)
    if ctx.trace is None or flops_per_token is None:
        return None
    calls = sum(1 for ops in ctx.trace.ops.values() for e in ops
                if op_kind(op_name(e.name)) == KERNEL)
    tokens = sum(rec.prompt_len if i == 0 else 1
                 for rec, i in ctx.traced_tokens())
    return roofline(ctx, tokens * flops_per_token(),
                    calls * ctx.work.expert_call_bytes(ctx.act_bytes),
                    KERNEL)
