"""100 × device time in collective ops / device busy time, the mean over
the devices that ran a collective."""
from bench import trace_reduce
from bench.metrics.common import per_device_mean


def read(obs):
    if obs.trace is None:
        return None
    share = per_device_mean([trace_reduce.collective_share(
        d, obs.trace_window) for d in obs.trace.devices])
    return None if share is None else 100.0 * share
