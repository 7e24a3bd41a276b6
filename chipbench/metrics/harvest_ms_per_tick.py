"""harvest_ms_per_tick: mean host milliseconds of a scheduler tick's
harvest (the slot arrays pulled to the host, finished requests completed,
their slots released), from the program's `repro.harvest` spans that start
inside the window. Moves served_tokens_per_s."""
from bench import trace


def read(tr, ctx):
    spans = trace.spans_in_window(tr, "repro.harvest")
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) / 1e6
