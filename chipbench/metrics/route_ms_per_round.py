"""route_ms_per_round: host milliseconds inside each tenant's begin_round
(relax, select and the first submit), per tenant-round of the window, from
the benchmark's own timer around the call. Moves round_p95_ms."""


def read(tr, ctx):
    spans = ctx.get("begin_s") or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
