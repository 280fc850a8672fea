"""Isolated matmul-shape study via xprof.

Times BERT-step-shaped dots as standalone jitted programs and reads the
per-fusion device times from the profiler, bypassing dispatch overhead
and dead-code elimination pitfalls.
"""
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as onp

from profile_common import load_hlo_stats  # noqa: E402


CASES = {}


def case(name, flops):
    def dec(fn):
        CASES[name] = (jax.jit(fn), flops)
        return fn
    return dec


B, L, D, H = 32, 512, 768, 3072
R = B * L
FL_WG = 2 * R * D * H


@case("wgrad r2 [16384,3072]T@[16384,768]", FL_WG)
def wg_r2(a, b):
    return a.reshape(R, H).T @ b.reshape(R, D)


@case("wgrad r3 [32,512,3072]x[32,512,768]", FL_WG)
def wg_r3(a, b):
    return jax.lax.dot_general(a, b, (((0, 1), (0, 1)), ((), ())))


@case("fwd r2 [16384,3072]@[3072,768]", FL_WG)
def fwd_r2(a, w):
    return a.reshape(R, H) @ w


@case("fwd r3 [32,512,3072]@[3072,768]", FL_WG)
def fwd_r3(a, w):
    return jnp.dot(a, w)


@case("dgrad r2 [16384,768]@[768,3072]", FL_WG)
def dg_r2(b, wt):
    return b.reshape(R, D) @ wt


@case("wgrad r2 f32out", FL_WG)
def wg_r2_f32(a, b):
    return jax.lax.dot_general(a.reshape(R, H).T, b.reshape(R, D),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


@case("square 4096^3", 2 * 4096 ** 3)
def sq(s, _):
    return s @ s


def main():
    rng = onp.random.RandomState(0)
    a = jnp.asarray(rng.randn(B, L, H), jnp.bfloat16)
    b = jnp.asarray(rng.randn(B, L, D), jnp.bfloat16)
    w = jnp.asarray(rng.randn(H, D), jnp.bfloat16)
    wt = jnp.asarray(rng.randn(D, H), jnp.bfloat16)
    s4 = jnp.asarray(rng.randn(4096, 4096), jnp.bfloat16)
    args = {
        "wgrad r2 [16384,3072]T@[16384,768]": (a, b),
        "wgrad r3 [32,512,3072]x[32,512,768]": (a, b),
        "fwd r2 [16384,3072]@[3072,768]": (a, w),
        "fwd r3 [32,512,3072]@[3072,768]": (a, w),
        "dgrad r2 [16384,768]@[768,3072]": (b, wt),
        "wgrad r2 f32out": (a, b),
        "square 4096^3": (s4, s4),
    }
    # warm/compile outside the trace
    for name, (fn, _) in CASES.items():
        onp.asarray(fn(*args[name]))[0]

    REP = 10
    logdir = tempfile.mkdtemp(prefix="mmshapes_")
    with jax.profiler.trace(logdir):
        outs = []
        for name, (fn, _) in CASES.items():
            for _ in range(REP):
                outs.append(fn(*args[name]))
        for o in outs:
            onp.asarray(o).ravel()[0]

    xp = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    cols, rows = load_hlo_stats(xp)
    i_name = cols.index("HLO op name")
    i_self = cols.index("Total self time (us)")
    i_prog = cols.index("Program id")
    # map each program (one per jit) to its heaviest op total
    byprog = {}
    for r in rows:
        byprog.setdefault(r[i_prog], []).append(r)
    # order of programs == compile order is not guaranteed; match by flops
    print("per-program heaviest ops:")
    for pid, rs in byprog.items():
        rs.sort(key=lambda r: -(r[i_self] or 0))
        top = rs[0]
        t_us = (top[i_self] or 0) / REP
        if t_us < 30:
            continue
        print(f"  prog {pid}: {t_us/1e3:7.3f} ms  {top[i_name]}")
    print("\ncase FLOPs for reference:")
    for name, (_, fl) in CASES.items():
        print(f"  {name:42s} {fl/1e9:8.1f} GFLOP "
              f"(1ms => {fl/1e-3/1e12:5.1f} TF/s)")


if __name__ == "__main__":
    main()
