#!/usr/bin/env python
"""Distributed job launcher (reference: ``tools/launch.py`` + the dmlc
tracker, SURVEY.md §3.4).

The reference spawns scheduler/server/worker processes over ssh/mpi/yarn with
``DMLC_*`` env rendezvous for the ps-lite parameter server.  TPU-native there
is no parameter server: every process runs the SAME SPMD program and joins a
JAX coordination service (``jax.distributed``), so the launcher's job is just
process bootstrap — start N workers with rendezvous env vars:

    python tools/launch.py -n 4 python train.py --kv-store dist_sync

Env protocol (read by ``mxnet_tpu.parallel.init_distributed``):
  MXNET_COORDINATOR   host:port of process 0's coordination service
  MXNET_NUM_WORKERS   total process count
  MXNET_WORKER_ID     this process's rank
(The DMLC_* names are also set for reference-script compatibility.)

Launchers: ``local`` forks N processes on this machine (the reference's
nightly-test pattern — multi-node semantics without a cluster); ``ssh``/
``mpi`` print the equivalent per-node command for external orchestration
(cluster schedulers own process placement on TPU pods).

``--launcher local -n N`` is the CPU-collectives path: it pins no chip per
worker, and a chip belongs to one process, so on a TPU host N workers would
all reach for the same chips and all but one fail or hang at backend
start-up.  Run it with ``JAX_PLATFORMS=cpu`` (tests/test_dist_launch.py
does).  One process drives all the chips of a host through
``parallel.make_mesh`` — that, not this launcher, is the one-host TPU path.
"""
import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=("local", "ssh", "mpi"),
                    default="local")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for workers")
    ap.add_argument("--hostfile", default=None,
                    help="(ssh/mpi) one host per line")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="failure recovery: on any worker death, abort the "
                         "whole job (a dead peer stalls collectives) and "
                         "relaunch up to N times; workers resume from their "
                         "latest checkpoint (checkpoint.elastic_run / "
                         "CheckpointManager.restore_latest)")
    ap.add_argument("--drain-timeout", type=float, default=300.0,
                    help="seconds workers may keep running after the first "
                         "worker finishes before the job is declared "
                         "stalled (a silent early exit-0 strands peers)")
    ap.add_argument("--barrier-timeout", type=float, default=None,
                    help="seconds before parallel.global_barrier declares a "
                         "peer dead and aborts this worker (exported as "
                         "MXNET_BARRIER_TIMEOUT)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no worker command given")

    def worker_env(rank, coord):
        env = dict(os.environ)
        env.update(e.split("=", 1) for e in args.env)
        env.update({
            "MXNET_COORDINATOR": coord,
            "MXNET_NUM_WORKERS": str(args.num_workers),
            "MXNET_WORKER_ID": str(rank),
            # reference-compat spellings (dmlc tracker protocol)
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_PS_ROOT_URI": coord.split(":")[0],
            "DMLC_PS_ROOT_PORT": coord.split(":")[1],
            "DMLC_ROLE": "worker",
        })
        if args.barrier_timeout:
            env["MXNET_BARRIER_TIMEOUT"] = str(args.barrier_timeout)
        return env

    if args.launcher != "local":
        port = _free_port()
        hosts = open(args.hostfile).read().split() if args.hostfile \
            else ["<host%d>" % i for i in range(args.num_workers)]
        print(f"# {args.launcher} launch plan (coordinator on {hosts[0]}):")
        for rank in range(args.num_workers):
            host = hosts[rank % len(hosts)]
            envs = " ".join(
                f"{k}={v}" for k, v in worker_env(rank, f"{hosts[0]}:{port}")
                .items() if k.startswith(("MXNET_", "DMLC_")))
            print(f"ssh {host} {envs} {' '.join(args.command)}")
        return 0

    def stop_all(procs):
        """SIGTERM, then SIGKILL stragglers — a worker wedged in a stalled
        collective (or with a graceful-drain SIGTERM handler that can't
        complete) must not hang the supervisor."""
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 15
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        return [p.wait() for p in procs]

    def run_attempt():
        """One job incarnation; returns exit codes.  Any worker death kills
        the rest — a dead peer would stall the others' collectives forever
        (the reference's dist_sync has the same failure mode, SURVEY §5.3).
        A worker that exits 0 while peers keep running past --drain-timeout
        counts as a death too (silent early departure stalls peers the same
        way)."""
        coordinator = f"127.0.0.1:{_free_port()}"
        procs = [subprocess.Popen(args.command, env=worker_env(r, coordinator))
                 for r in range(args.num_workers)]
        try:
            return _supervise(procs)
        except KeyboardInterrupt:
            stop_all(procs)
            raise

    def _supervise(procs):
        drain_start = None
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                return codes
            if any(c not in (None, 0) for c in codes):
                dead = [i for i, c in enumerate(codes) if c not in (None, 0)]
                print(f"launch: worker(s) {dead} died "
                      f"(codes {[codes[i] for i in dead]}); aborting job",
                      file=sys.stderr)
                return stop_all(procs)
            if any(c == 0 for c in codes):
                drain_start = drain_start or time.time()
                if time.time() - drain_start > args.drain_timeout:
                    slow = [i for i, c in enumerate(codes) if c is None]
                    print(f"launch: worker(s) {slow} still running "
                          f"{args.drain_timeout:.0f}s after first worker "
                          "finished (stalled on a departed peer?); "
                          "aborting job", file=sys.stderr)
                    codes = stop_all(procs)
                    # count the stall itself as the failure
                    return [c if c != 0 else 1 for c in codes]
            time.sleep(0.2)

    for attempt in range(args.max_restarts + 1):
        try:
            codes = run_attempt()
        except KeyboardInterrupt:
            print("launch: interrupted; stopping job", file=sys.stderr)
            return 130
        bad = [c for c in codes if c != 0]
        if not bad:
            return 0
        print(f"launch: {len(bad)}/{len(codes)} workers failed "
              f"(attempt {attempt + 1}/{args.max_restarts + 1})",
              file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
