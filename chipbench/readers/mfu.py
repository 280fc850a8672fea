"""Model FLOP/s utilization: the operations the mathematics of one run of
a program requires on one chip (the job's ``required`` reading; recompute
not counted) over the program's start-to-start period in the device trace,
over the bf16 peak.  Percent."""
from .. import trace_reduce as tr
from . import per_device


def read(metric, obs):
    role = metric["params"]["role"]
    need = obs["readings"].get("required", {}).get(role)
    period = per_device(obs, lambda d, roles: tr.mean_period_ms(
        roles.get(role, [])))
    if period is None or not need or not obs["peaks"]:
        return None
    return 100.0 * need["flops"] / (period / 1e3) \
        / obs["peaks"]["bf16_flops_per_s"]
