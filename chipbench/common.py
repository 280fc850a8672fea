"""What every part of chipbench shares: the data files and how a generator,
a reader or a job is found by name, the device check against
``peaks.json``, the seed folding, percentiles and the one-record-per-line
printer.  Nothing here touches jax until ``device_record`` is called."""
import importlib
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT_DIR = os.path.join(REPO, ".chipbench_out")   # traces; git-ignored
T_PROCESS_START = time.perf_counter()            # set-up counts from here


def say(**record):
    print(json.dumps(record, separators=(",", ":"), default=str), flush=True)


def fail(message):
    raise SystemExit(f"chipbench: FAILED: {message}")


def load(kind, name):
    """``chipbench/<kind>/<name>.json`` as a dict: kind is one of configs,
    traffic, workloads, metrics."""
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        fail(f"no {kind} file {os.path.relpath(path, REPO)}")
    with open(path) as f:
        return json.load(f)


def names(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(HERE, kind))
                  if f.endswith(".json"))


def plugin(kind, name):
    """Module ``chipbench/<kind>/<name>.py``: kind is jobs, generators or
    readers.  A later PR adds one by adding the file."""
    if not os.path.isfile(os.path.join(HERE, kind, name + ".py")):
        fail(f"no {kind[:-1]} named {name!r} under chipbench/{kind}/")
    return importlib.import_module(f"chipbench.{kind}.{name}")


def fold_seed(seed, stream=0):
    """--seed may exceed 32 signed bits; numpy and mx.random want less.
    ``stream`` separates the draws (weights, sizes, tokens) of one seed."""
    return (int(seed) * 1000003 + 7919 * int(stream)) % (2 ** 31 - 1)


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list, q in (0, 100]."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, -(-len(s) * q // 100) - 1))
    return s[int(k)]


def peaks_for(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    return table.get(kind)


def device_record(chips, rehearse):
    """State what jax found; refuse anything that is not the accelerator
    the cell asks for, with a row in peaks.json.  Returns (device dict,
    peaks row or None when rehearsing), and the jax devices to use."""
    import jax
    devs = jax.devices()
    d = devs[0]
    dev = {"platform": d.platform, "kind": d.device_kind, "count": chips}
    say(device=dict(dev, found=len(devs)), jax=jax.__version__,
        rehearse=bool(rehearse))
    if len(devs) < chips:
        fail(f"the cell asks for {chips} chips, jax found {len(devs)}")
    peaks = peaks_for(d.device_kind)
    if not rehearse:
        if d.platform != "tpu" or jax.default_backend() != "tpu":
            fail(f"no accelerator: jax found platform {d.platform!r}")
        if peaks is None:
            fail(f"device_kind {d.device_kind!r} has no row in "
                 f"chipbench/peaks.json")
    return dev, peaks, devs[:chips]


def memory_peak_bytes(devs):
    """Peak bytes on the fullest chip as its allocator counts them: the
    peak in use plus the peak reserved for running programs' temporaries,
    which ``peak_bytes_in_use`` leaves out (BERT-base b32: 1.27 GB in use,
    4.13 GB reserved; my chip run, PR 24).  0 where the backend keeps no
    statistics, as the CPU does."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def count_compiles():
    """Counter of XLA backend compilations in this process from now on."""
    import jax
    n = [0]

    def on_event(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return n
