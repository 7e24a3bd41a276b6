"""device_idle.served: the device's idle share of the served window, 1 -
busy union / window, from the profiler trace. Moves served_tokens_per_s."""
from bench import trace


def read(tr, ctx):
    return trace.idle_share(tr)
