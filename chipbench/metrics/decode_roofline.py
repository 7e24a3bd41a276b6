"""decode_roofline: the decode programs' share of their roofline. For every
decode chunk the window dispatched, the least time of each step is the
larger of its FLOPs over the chip's peak and its bytes over the chip's
bandwidth, from the reference's work functions: the weights once a step,
each live row's KV positions so far (or its SSD state read and written) and
its token's FLOPs. The sum of least times over the summed device time of the
decode programs in the trace. Moves served_tokens_per_s."""
from bench import trace

PROGRAM = "jit__decode_chunk"


def least_seconds(ctx):
    ref, peaks = ctx["reference"], ctx["peaks"]
    total = 0.0
    for member, rows in ctx["decodes"]:
        a = ctx["members"][member]
        steps = max((live for _, live in rows), default=0)
        for j in range(steps):
            ctxs = [pos + j + 1 for pos, live in rows if j < live]
            flops = sum(ref.flops_per_token(a, c) for c in ctxs)
            nbytes = ref.decode_step_bytes(a, ctxs)
            total += max(flops / peaks.bf16_flops,
                         nbytes / peaks.hbm_bytes_per_s)
    return total


def read(tr, ctx):
    events = trace.modules_named(tr, PROGRAM)
    device = sum(trace.clipped_ns(e, tr.window) for e in events) / 1e9
    if device <= 0 or not ctx.get("decodes"):
        return None
    return 100.0 * least_seconds(ctx) / device
