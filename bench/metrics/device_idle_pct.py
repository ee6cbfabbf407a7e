"""100 × (1 − union of device-op intervals / traced window), the mean
over the cell's devices."""
from bench import trace_reduce


def read(obs):
    if obs.trace is None or not obs.trace.devices:
        return None
    return 100.0 * trace_reduce.idle_share(obs.trace, obs.trace_window)
