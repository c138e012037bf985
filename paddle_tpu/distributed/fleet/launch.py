"""Launcher (reference `fleet/launch.py:208` launch_collective / :260
launch_ps, `launch_utils.py:435,494` start_local_trainers).

TPU model: ONE process per host (SPMD spans local chips), so the launcher
spawns one worker per node entry, wiring the same PADDLE_* env contract
plus JAX coordinator vars. `--nproc_per_node > 1` is for CPU multi-process
runs (`JAX_PLATFORMS=cpu`); on a host with TPU chips it is refused, because
the children would all open the same chips
(`distributed.env.refuse_processes_per_chip`). The launcher itself never
touches a JAX backend. Usage:
  python -m paddle_tpu.distributed.fleet.launch --nproc_per_node 1 train.py
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _parse():
    p = argparse.ArgumentParser("fleet launch")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma-separated host ips")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--gpus", type=str, default=None,
                   help="parity alias; selects device count per proc")
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--run_mode", type=str, default="collective")
    p.add_argument("--elastic", action="store_true",
                   help="start a KV heartbeat monitor: ranks that die, "
                        "fail init, or stop beating fault the job (an "
                        "in-process deadlock needs the manual touch() "
                        "mode — see fleet/elastic.py)")
    p.add_argument("--elastic_timeout", type=float, default=30.0)
    p.add_argument("--elastic_grace", type=float, default=120.0,
                   help="seconds a rank may take to its FIRST beat "
                        "(jax/backend init is slow)")
    p.add_argument("--servers", type=str, default="")
    p.add_argument("--workers", type=str, default="")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args()


def _spawn_procs(args):
    ips = args.ips.split(",")
    nproc = args.nproc_per_node
    from ..env import refuse_processes_per_chip
    refuse_processes_per_chip(nproc, "fleet.launch --nproc_per_node")
    world = len(ips) * nproc
    endpoints = [f"{ip}:{args.started_port + i}"
                 for ip in ips for i in range(nproc)]
    os.makedirs(args.log_dir, exist_ok=True)
    kv_ep = None
    if getattr(args, "elastic", False):
        from .http_server import KVServer
        kv = KVServer().start()
        kv_ep = f"127.0.0.1:{kv.port}"
    procs = []
    # this launcher instance only starts local ranks (reference behavior)
    local_base = ips.index("127.0.0.1") * nproc if "127.0.0.1" in ips else 0
    coordinator = endpoints[0]
    for i in range(nproc):
        rank = local_base + i
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(world),
            "JAX_PROCESS_ID": str(rank),
            "TRAINING_ROLE": "TRAINER",
        })
        if kv_ep:
            env["PADDLE_ELASTIC_KV"] = kv_ep
        logf = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "w")
        cmd = [sys.executable, "-u", args.training_script] + \
            args.training_script_args
        procs.append((subprocess.Popen(cmd, env=env, stdout=logf,
                                       stderr=subprocess.STDOUT), logf,
                      rank))
    local_ranks = [r for _, _, r in procs]
    return procs, kv_ep, local_ranks


def _watch(procs):
    """reference `launch_utils.py:526 watch_local_trainers`: abort the job
    if any child dies."""
    try:
        while procs:
            alive = []
            for p, logf, rank in procs:
                ret = p.poll()
                if ret is None:
                    alive.append((p, logf, rank))
                elif ret != 0:
                    print(f"[fleet.launch] rank {rank} FAILED "
                          f"(exit {ret}); terminating job", file=sys.stderr)
                    for q, _, _ in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    sys.exit(ret)
            procs = alive
            time.sleep(1)
    except KeyboardInterrupt:
        for p, _, _ in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        raise


def launch():
    args = _parse()
    procs, kv_ep, local_ranks = _spawn_procs(args)
    if kv_ep:
        # liveness on top of the exit watchdog: a local rank that dies,
        # fails init, or stops beating faults the whole job. Only LOCAL
        # ranks are watched — the KV is loopback; each node's launcher
        # watches its own ranks (reference watch_local_trainers scope).
        from .elastic import ElasticManager

        def on_fault(dead):
            print(f"[fleet.launch] rank(s) {dead} stopped heartbeating; "
                  f"terminating job", file=sys.stderr)
            for p, _, _ in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
        ElasticManager(kv_ep, ranks=local_ranks,
                       timeout=args.elastic_timeout,
                       grace=args.elastic_grace).watch(on_fault=on_fault)
    _watch(procs)


main = launch

if __name__ == "__main__":
    launch()
