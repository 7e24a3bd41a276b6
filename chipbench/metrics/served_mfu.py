"""served_mfu: the whole served step's share of the chip's peak: model FLOPs
of every token the window served (each finished request's prompt and
generated tokens, each at its own context length, from the reference's
work function) over the window's seconds and the chip's bf16 peak. Bounds a
kernel's gain once the kernel is replaced. Moves served_tokens_per_s."""


def read(tr, ctx):
    flops = ctx.get("model_flops", 0.0)
    if not flops:
        return None
    return 100.0 * flops / ctx["wall_s"] / ctx["peaks"].bf16_flops
