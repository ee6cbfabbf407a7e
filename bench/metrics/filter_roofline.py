"""The filter's share of its roofline: the least time of the work of the
filter calls that ran in the traced window, over the device time of those
calls (the runs of the programs whose ops carry a ``repro.filter2d.*``
scope; on several chips each side is per device, halo exchange
included), the mean over the devices.

The work is the algorithm's (``bench/roofline.py``), not any plan's, so a
change of executor shows here as a change of time.
"""
from bench import roofline, trace_reduce
from bench.metrics.common import per_device_mean


def _per_device(obs):
    least = roofline.least_time(obs.work_per_call, obs.device_kind)
    out = []
    for d in obs.trace.devices:
        runs = trace_reduce.filter_runs(d, obs.trace_window)
        busy = sum(e - s for s, e, _ in runs) * 1e-9
        out.append((len(runs), busy, least))
    return out


def read(obs):
    if obs.trace is None or obs.work_per_call is None:
        return None
    shares = [100.0 * n * least["seconds"] / busy
              for n, busy, least in _per_device(obs) if n and busy > 0]
    return per_device_mean(shares)


def describe(obs) -> str:
    n, busy, least = _per_device(obs)[0]
    return (f"bound={least['bound']} least_us_per_call="
            f"{least['seconds'] * 1e6} (compute {least['compute_s'] * 1e6},"
            f" memory {least['memory_s'] * 1e6}) calls={n} "
            f"filter_device_s={busy} (device 0)")
