"""queue_wait_ms: host milliseconds a request waited in the scheduler's
queue before its admission, from the program's `repro.admit` spans (one per
prefill bucket) that start inside the window: the sum of their `wait_us`
counts (each the bucket's summed wait, from queueing, again on a retry)
over the sum of their `requests`. Moves round_p95_ms."""
from bench import trace


def read(tr, ctx):
    spans = trace.spans_in_window(tr, "repro.admit")
    requests = sum(int(s.stats["requests"]) for s in spans)
    if not requests:
        return None
    return sum(float(s.stats["wait_us"]) for s in spans) / requests / 1e3
