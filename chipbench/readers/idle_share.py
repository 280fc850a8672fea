"""1 - (union of the intervals in which an op ran on the device) / traced
window, in percent, averaged over the chips used."""
from .. import trace_reduce as tr


def read(metric, obs):
    if not obs["trace"] or not obs["trace"]["devices"]:
        return None
    busy, window = tr.busy_and_window_s(obs["trace"])
    return 100.0 * (1.0 - busy / window) if window > 0 else None
