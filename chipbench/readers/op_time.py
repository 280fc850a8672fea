"""Milliseconds per run of a role's program spent in a class of ops:
``mosaic`` (Pallas kernels: ``tpu_custom_call`` custom calls) or
``collective_exposed`` (collectives while no other op runs on that chip;
None on one chip, where there are none)."""
from .. import trace_reduce as tr
from . import per_device


def read(metric, obs):
    p = metric["params"]
    if p["select"] == "collective_exposed":
        if obs["chips"] < 2:
            return None
        return per_device(obs, lambda d, roles: tr.exposed_collective_ms(
            d, roles.get(p["role"], [])))
    return per_device(obs, lambda d, roles: tr.ops_ms_per_module(
        d, roles.get(p["role"], []), lambda o: o[4]))
