"""INT8 serving benchmark: quantized vs bf16 vs fp32 ResNet-50 inference.

Runs on whatever device jax selects (pass --cpu-mesh 1 for a CPU smoke
run).  Post-training quantization via
``contrib.quantize_net`` (minmax calibration on synthetic data) — the
int8 path drives the MXU at double rate with fp32 dequantize epilogues.
"""
import argparse
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--cpu-mesh", type=int, default=0)
    args = ap.parse_args()
    if args.cpu_mesh:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.gluon.model_zoo import get_model

    B = args.batch_size
    rng = onp.random.RandomState(0)
    x_np = rng.randn(B, 3, args.image_size, args.image_size).astype("float32")

    def bench(net, x, tag):
        net.hybridize(static_alloc=True)
        # several warmup batches: the first executions after compile carry
        # one-time costs (program upload/autotune) well beyond the first call
        for _ in range(10):
            out = net(x)
        float(out.asnumpy().ravel()[0])
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = net(x)
        float(out.asnumpy().ravel()[0])
        dt = (time.perf_counter() - t0) / args.steps
        print(f"{tag:22s} {B / dt:9.1f} img/s   ({dt * 1e3:.2f} ms/batch)")
        return B / dt

    results = {}
    for tag, dtype in (("fp32", "float32"), ("bfloat16", "bfloat16")):
        mx.random.seed(0)
        net = get_model(args.model, classes=1000)
        net.initialize()
        if dtype != "float32":
            net.cast(dtype)
        x = nd.array(x_np).astype(dtype)
        results[tag] = bench(net, x, f"{args.model} {tag}")

    mx.random.seed(0)
    net = get_model(args.model, classes=1000)
    net.initialize()
    calib = nd.array(x_np[:32])
    q.quantize_net(net, calib_data=[calib], calib_mode="naive")
    results["int8"] = bench(net, nd.array(x_np), f"{args.model} int8")
    print(f"int8 speedup vs fp32: {results['int8'] / results['fp32']:.2f}x, "
          f"vs bf16: {results['int8'] / results['bfloat16']:.2f}x")


if __name__ == "__main__":
    main()
