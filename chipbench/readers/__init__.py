"""One small reader per kind of per-layer metric.  ``read(metric, obs)``
takes the metric's own file (``chipbench/metrics/<name>.json``, whose
``params`` are the reader's) and what the run observed: the reduced trace
(``obs["trace"]``, None in an untraced or device-less run), the job's
``readings``, the peaks row, the chips.  It returns the number, or None
where there is nothing to read, and the harness then leaves the metric out
of the line."""


def per_device(obs, fn):
    """Mean over the device planes of ``fn(device plane, its modules by
    role)``, None if the trace has no device plane or any plane has none."""
    from .. import trace_reduce
    trace = obs["trace"]
    if not trace or not trace["devices"]:
        return None
    values = [fn(d, trace_reduce.modules_by_role(d, obs["readings"]["roles"]))
              for d in trace["devices"]]
    if any(v is None for v in values):
        return None
    return sum(values) / len(values)
