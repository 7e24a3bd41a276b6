"""feedback_ms_per_completion: mean host milliseconds of the router's
completion callback (`MultiLLMService._on_complete`: the answer's quality
and cost, the bandit's record and, in a cascade, the next request), from
the program's `repro.feedback` spans that start inside the window. Moves
round_p95_ms."""
from bench import trace


def read(tr, ctx):
    spans = trace.spans_in_window(tr, "repro.feedback")
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) / 1e6
