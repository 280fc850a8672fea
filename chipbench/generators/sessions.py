"""Serving traffic from a data file: sessions that each send their
requests one after another, started on a schedule.

A closed loop of N clients is N sessions, started over ``ramp_s``, that
run until the window closes (a client's requests repeat with period
``requests_per_session``); an open loop is one session of one request per
arrival.  Sizes and arrival gaps are evenly spaced quantiles of the stated
distributions, made once from ``sizes_seed``; ``--seed`` only deals them
out in another order and draws the token ids, so every seed offers the
same work.

    arrivals: {"kind": "closed", "clients": 128, "ramp_s": 4.0,
               "requests_per_session": 4}
              {"kind": "poisson" | "gamma", "rate_per_s": 11.0, "cv": 1.0,
               "horizon_s": 40.0}
    prompt_len / output_len: {"dist": "uniform" | "loguniform",
                              "lo": 8, "hi": 128}
    max_total: prompt + output at most this (the output is cut to fit)
"""
import math

import numpy as onp


def _quantiles(spec, n, rng):
    u = (onp.arange(n) + 0.5) / n
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "loguniform":
        x = onp.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    elif spec["dist"] == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return rng.permutation(onp.clip(onp.floor(x), lo, hi).astype(int))


def _gaps(arr, n, rng):
    """n inter-arrival gaps with mean 1/rate: the means of n quantile bins
    of a gamma distribution with the stated coefficient of variation
    (1 gives the exponential gaps of a Poisson process)."""
    k = 1.0 / float(arr.get("cv", 1.0)) ** 2
    bins = onp.sort(rng.gamma(k, 1.0 / k, size=64 * n)).reshape(n, 64)
    gaps = bins.mean(axis=1)
    return gaps / gaps.mean() / arr["rate_per_s"]


def sessions(traffic, seed):
    """[{"start_s": s, "repeat": bool, "requests": [[prompt_len,
    max_new_tokens, token_seed], ...]}, ...] in order of start."""
    arr = traffic["arrivals"]
    fixed = onp.random.RandomState(traffic["sizes_seed"])
    deal = onp.random.RandomState(seed)
    closed = arr["kind"] == "closed"
    if closed:
        n_sessions, per = arr["clients"], arr["requests_per_session"]
    else:
        n_sessions, per = int(arr["rate_per_s"] * arr["horizon_s"]), 1
    n = n_sessions * per
    prompts = _quantiles(traffic["prompt_len"], n, fixed)
    outputs = _quantiles(traffic["output_len"], n, fixed)
    outputs = onp.minimum(outputs, traffic["max_total"] - prompts)
    if closed:
        starts = fixed.permutation(
            arr["ramp_s"] * onp.arange(n_sessions) / n_sessions)
        # a client found mid-request, as a loop that has run for hours
        # would be: its first answer is a uniform share of the drawn one
        residual = fixed.permutation((onp.arange(n_sessions) + 0.5)
                                     / n_sessions)
    else:
        starts = onp.cumsum(deal.permutation(_gaps(arr, n_sessions, fixed)))
    order = deal.permutation(n)
    token_seeds = deal.randint(0, 2 ** 31 - 1, size=n)
    out = []
    for s in range(n_sessions):
        reqs = []
        for k in range(per):
            i = order[s * per + k]
            new = int(outputs[i])
            if closed and k == 0:
                new = max(1, int(math.ceil(new * residual[s])))
            reqs.append([int(prompts[i]), new, int(token_seeds[i])])
        out.append({"start_s": float(starts[s]), "repeat": closed,
                    "requests": reqs})
    return sorted(out, key=lambda x: x["start_s"])


def prompt_tokens(vocab, token_seed, n):
    return onp.random.RandomState(token_seed).randint(0, vocab, n).tolist()
