"""Input-pipeline decode+augment scaling vs preprocess_threads.

Measures the NATIVE path (C++ RecordIO read -> libjpeg decode -> fused
augment) in ms/batch at several thread counts on THIS host.  On the 1-vCPU
dev VM this yields the single-core constant plus the (absence of) thread
overhead — the core-scaling curve should be refreshed on a many-core box
with the same script.

``--record`` appends an ``io_scaling`` record through io_overlap's shared
atomic-writer helper (``util.write_json_records``; bench.py's rewrite
preserves ``io_*`` records).

Usage: python benchmark/io_scaling.py [--n 64] [--batch 32] [--size 224]
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--threads", default="1,2,4")
    ap.add_argument("--record", action="store_true",
                    help="append the io_scaling record to "
                    "BENCH_DETAILS.json (atomic writer)")
    args = ap.parse_args()

    from mxnet_tpu import runtime
    from mxnet_tpu.io import ImageRecordIter
    from mxnet_tpu.recordio import IRHeader, MXIndexedRecordIO, pack_img
    if not runtime.available() or not runtime.Features().is_enabled("JPEG"):
        raise SystemExit("native jpeg pipeline not built")

    tmp = tempfile.mkdtemp()
    rec, idx = os.path.join(tmp, "a.rec"), os.path.join(tmp, "a.idx")
    rng = onp.random.RandomState(0)
    w = MXIndexedRecordIO(idx, rec, "w")
    for i in range(args.n):
        img = (rng.rand(args.size, args.size, 3) * 255).astype("uint8")
        w.write_idx(i, pack_img(IRHeader(0, float(i), i, 0), img,
                                quality=90, img_fmt=".jpg"))
    w.close()

    print(f"{args.n} JPEGs {args.size}x{args.size}, batch {args.batch}, "
          f"host cores: {os.cpu_count()}")
    results = {}
    for nt in [int(t) for t in args.threads.split(",")]:
        it = ImageRecordIter(path_imgrec=rec, data_shape=(3, args.size,
                                                          args.size),
                             batch_size=args.batch, preprocess_threads=nt)
        # warm (first batch pays arena setup)
        it.next()
        t0 = time.perf_counter()
        nb = 0
        try:
            while True:
                b = it.next()
                b.data[0].asnumpy()[0, 0, 0, 0]
                nb += 1
        except StopIteration:
            pass
        dt = (time.perf_counter() - t0) / max(nb, 1)
        results[nt] = round(dt * 1e3, 2)
        print(f"  preprocess_threads={nt}: {dt * 1e3:8.1f} ms/batch "
              f"({args.batch / dt:.1f} img/s)")

    if args.record:
        from io_overlap import record
        record("io_scaling", min(results.values()), "ms/batch",
               size=args.size, batch=args.batch, n=args.n,
               host_cores=os.cpu_count(),
               ms_per_batch_by_threads={str(k): v
                                        for k, v in results.items()})


if __name__ == "__main__":
    main()
