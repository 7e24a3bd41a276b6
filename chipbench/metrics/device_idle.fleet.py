"""device_idle.fleet: the device's idle share of the fleet window, 1 - busy
union / window, from the profiler trace. Moves fleet_rounds_per_s."""
from bench import trace


def read(tr, ctx):
    return trace.idle_share(tr)
