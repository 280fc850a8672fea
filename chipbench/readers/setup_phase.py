"""Seconds of set-up this run paid in some of its phases: those that
``params.keys`` lists, else the ones the job names as compilation (lower +
compile, or building an engine that compiles or warm-loads its
programs)."""


def read(metric, obs):
    r = obs["readings"]
    keys = metric["params"].get("keys") or r.get("compile_keys") or []
    if not keys or any(k not in r["phases"] for k in keys):
        return None
    return sum(r["phases"][k] for k in keys)
