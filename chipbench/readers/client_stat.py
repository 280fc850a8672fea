"""From the load generator's stamps.  ``wire_ttft``: median time to first
token as the clients saw it less the median the engine itself reported for
the same requests: HTTP, JSON and the handler thread.  ``late_p95``: 95th
percentile of sent - due, how late the generator ran (open loops only: in
a closed loop a request is due when it is sent)."""
from ..common import percentile


def read(metric, obs):
    r = obs["readings"]
    which = metric["params"]["stat"]
    if which == "wire_ttft":
        if not r.get("client_ttft_ms") or not r.get("engine_ttft_ms"):
            return None
        return percentile(r["client_ttft_ms"], 50) \
            - percentile(r["engine_ttft_ms"], 50)
    if which == "late_p95":
        if not r.get("late_ms") or not r.get("open_loop"):
            return None
        return percentile(r["late_ms"], 95)
    return None
