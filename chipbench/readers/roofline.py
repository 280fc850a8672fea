"""A program's share of its roofline: the least time the chip's peaks
allow for the operations and bytes the mathematics of one run requires on
one chip (the job's ``required`` reading, from ``chipbench/required.py``),
over the program's measured device time.  Percent; which peak binds is
printed on an earlier line."""
from .. import required
from .. import trace_reduce as tr
from ..common import say
from . import per_device


def read(metric, obs):
    role = metric["params"]["role"]
    need = obs["readings"].get("required", {}).get(role)
    measured = per_device(obs, lambda d, roles: tr.mean_duration_ms(
        roles.get(role, [])))
    if measured is None or not need or not obs["peaks"]:
        return None
    least, bound = required.roofline_ms(need["flops"], need["bytes"],
                                        obs["peaks"])
    say(roofline=metric["name"], bound=bound, least_ms=least,
        measured_ms=measured, required_flops=need["flops"],
        required_bytes=need["bytes"])
    return 100.0 * least / measured
