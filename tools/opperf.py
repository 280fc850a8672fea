#!/usr/bin/env python
"""Per-operator micro-benchmark harness (reference: upstream's ``opperf``
package — `run_benchmark_operators`, SURVEY.md §6).

Measures each registered op up to four ways (``--modes``):

* ``eager``  — imperative NDArray call with the per-op executable cache
  disabled: full un-jitted JAX dispatch per call (the pre-LazyEngine
  baseline; dominated by per-call tracing + device dispatch latency)
* ``cached`` — the same imperative call through the engine's per-op
  executable cache (``MXNET_OP_CACHE``, docs/ENGINE.md) — the default
  eager path since the LazyEngine PR
* ``lazy``   — calls recorded into a lazy segment (``engine.bulk``) and
  flushed as one fused jit program: per-call cost is amortized recording
  plus 1/runs of a single compiled dispatch
* ``fused``  — marginal cost inside one compiled loop (``lax.scan``), i.e.
  the op's steady-state device cost inside a hybridized program

It compares dispatch paths op by op on whatever backend JAX finds; it is
no record of the system's speed (that is ``chipbench/``, PERF.md).

Usage:
    python tools/opperf.py                         # default op set, all modes
    python tools/opperf.py --ops dot,relu --modes eager,lazy --json r.json
    python tools/opperf.py --cpu                   # force CPU
"""
import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def default_configs():
    """(op display name, builder(nd) -> (fn, args)) — shapes follow the
    reference opperf defaults (1024-ish tensors, conv on 224 images)."""
    B = 32

    def u(shape):
        return onp.random.RandomState(0).randn(*shape).astype("float32")

    cfgs = []

    def add(name, make):
        cfgs.append((name, make))

    for op in ["relu", "sigmoid", "tanh", "exp", "log", "sqrt", "square"]:
        add(f"{op} (1024x1024)",
            lambda nd, op=op: (getattr(nd, op), (nd.array(u((1024, 1024))),)))
    for op in ["broadcast_add", "broadcast_mul", "broadcast_maximum"]:
        add(f"{op} (1024x1024)",
            lambda nd, op=op: (getattr(nd, op),
                               (nd.array(u((1024, 1024))),
                                nd.array(u((1024, 1024))))))
    add("sum (1024x1024, axis=1)",
        lambda nd: (lambda x: nd.sum(x, axis=1),
                    (nd.array(u((1024, 1024))),)))
    add("dot (1024x1024)",
        lambda nd: (nd.dot, (nd.array(u((1024, 1024))),
                             nd.array(u((1024, 1024))))))
    add("batch_dot (32x128x128)",
        lambda nd: (nd.batch_dot, (nd.array(u((32, 128, 128))),
                                   nd.array(u((32, 128, 128))))))
    add("FullyConnected (32x1024 -> 1024)",
        lambda nd: (lambda x, w: nd.FullyConnected(x, w, num_hidden=1024,
                                                   no_bias=True),
                    (nd.array(u((B, 1024))), nd.array(u((1024, 1024))))))
    add("Convolution 3x3 (32x64x56x56)",
        lambda nd: (lambda x, w: nd.Convolution(
            x, w, kernel=(3, 3), num_filter=64, pad=(1, 1), no_bias=True),
            (nd.array(u((B, 64, 56, 56))), nd.array(u((64, 64, 3, 3))))))
    add("Pooling max 2x2 (32x64x56x56)",
        lambda nd: (lambda x: nd.Pooling(x, kernel=(2, 2), pool_type="max",
                                         stride=(2, 2)),
                    (nd.array(u((B, 64, 56, 56))),)))
    add("BatchNorm (32x64x56x56)",
        lambda nd: (lambda x, g, b, m, v: nd.BatchNorm(x, g, b, m, v),
                    (nd.array(u((B, 64, 56, 56))), nd.array(u((64,))),
                     nd.array(u((64,))), nd.array(u((64,))),
                     nd.array(onp.abs(u((64,)))))))
    add("softmax (32x1024)",
        lambda nd: (lambda x: nd.softmax(x, axis=-1),
                    (nd.array(u((B, 1024))),)))
    add("transpose (1024x1024)",
        lambda nd: (lambda x: nd.transpose(x, (1, 0)),
                    (nd.array(u((1024, 1024))),)))
    add("topk k=10 (32x1024)",
        lambda nd: (lambda x: nd.topk(x, k=10, axis=-1),
                    (nd.array(u((B, 1024))),)))
    return cfgs


def _sync(out):
    from mxnet_tpu.ndarray.ndarray import NDArray
    o = out[0] if isinstance(out, (tuple, list)) else out
    if isinstance(o, NDArray):
        o.wait_to_read()
        onp.asarray(o.asnumpy().ravel()[:1])


def bench_eager(fn, args, runs=20, warmup=5, op_cache=False):
    """Imperative per-call timing.  ``op_cache=False`` measures the
    un-jitted baseline (the historical 'eager' column); ``True`` measures
    the engine's per-op executable cache (the current default path)."""
    from mxnet_tpu import engine
    with engine.op_cache_scope(op_cache):
        for _ in range(warmup):
            out = fn(*args)
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(runs):
            out = fn(*args)
        _sync(out)
        return (time.perf_counter() - t0) / runs


def bench_lazy(fn, args, runs=20, warmup=2):
    """Per-call cost when ``runs`` calls are recorded into one lazy
    segment and flushed as a single fused jit program at the sync point."""
    from mxnet_tpu import engine

    def once():
        with engine.bulk(runs + 1):
            for _ in range(runs):
                out = fn(*args)
            _sync(out)
        return out

    for _ in range(max(warmup, 2)):   # >=2: stabilizes the liveness key
        once()
    t0 = time.perf_counter()
    once()
    return (time.perf_counter() - t0) / runs


def bench_fused(fn, args, iters_a=4, iters_b=20):
    """Marginal per-iteration cost inside one jitted scan."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray.ndarray import NDArray, unwrap

    raws = tuple(unwrap(a) for a in args)

    def make(n_iters):
        def run(*raws_in):
            def body(c, _):
                shifted = (raws_in[0] + c,) + raws_in[1:]
                out = fn(*[NDArray(r) for r in shifted])
                o = unwrap(out[0] if isinstance(out, (tuple, list)) else out)
                # depend on the WHOLE output: a single-element dependency
                # lets XLA dead-code-eliminate most of the op
                delta = (o.astype(jnp.float32).sum() * 1e-20) \
                    .astype(raws_in[0].dtype)
                return c + delta, ()
            c, _ = jax.lax.scan(body, jnp.zeros((), raws[0].dtype), None,
                                length=n_iters)
            return c
        return jax.jit(run)

    def t(f):
        r = f(*raws); onp.asarray(r)
        t0 = time.perf_counter()
        for _ in range(5):
            r = f(*raws)
        onp.asarray(r)
        return (time.perf_counter() - t0) / 5

    ta = t(make(iters_a))
    tb = t(make(iters_b))
    return max((tb - ta) / (iters_b - iters_a), 0.0)


_ALL_MODES = ("eager", "cached", "lazy", "fused")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated substrings to filter ops")
    ap.add_argument("--modes", default="eager,cached,lazy,fused",
                    help=f"comma-separated subset of {_ALL_MODES}")
    ap.add_argument("--json", default=None, help="write results to file")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--no-fused", action="store_true",
                    help="skip the compiled-loop marginal measurement")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu import nd

    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    bad = [m for m in modes if m not in _ALL_MODES]
    if bad:
        ap.error(f"unknown mode(s) {bad}; choose from {_ALL_MODES}")
    if args.no_fused and "fused" in modes:
        modes.remove("fused")

    results = []
    sel = [s.strip().lower() for s in args.ops.split(",")] if args.ops else None
    print(f"platform: {jax.devices()[0].platform}", flush=True)
    print(f"{'op':40s} " + " ".join(f"{m + ' ms':>11s}" for m in modes),
          flush=True)
    bench = {
        "eager": lambda fn, fa: bench_eager(fn, fa, op_cache=False),
        "cached": lambda fn, fa: bench_eager(fn, fa, op_cache=True),
        "lazy": bench_lazy,
        "fused": bench_fused,
    }
    for name, make in default_configs():
        if sel and not any(s in name.lower() for s in sel):
            continue
        fn, fargs = make(nd)
        row = {"op": name}
        for m in modes:
            row[f"{m}_ms"] = bench[m](fn, fargs) * 1e3
        print(f"{name:40s} " + " ".join(f"{row[m + '_ms']:11.4f}"
                                        for m in modes), flush=True)
        results.append(row)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
