"""Process-level distributed env (reference
`python/paddle/distributed/parallel.py:57` init_parallel_env +
`fleet/base/role_maker.py:528` PaddleCloudRoleMaker env parsing).

TPU model: one process per HOST (not per chip — SPMD covers local chips);
rendezvous = jax.distributed.initialize with a coordinator address. The
same PADDLE_* env vars the reference launcher sets are honored.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["ParallelEnv", "init_parallel_env", "get_rank", "get_world_size",
           "is_initialized", "refuse_processes_per_chip"]

_initialized = False


def refuse_processes_per_chip(nprocs: int, what: str, env=None) -> None:
    """Raise before `what` starts `nprocs` > 1 local JAX processes on a
    host with TPU chips.

    A chip belongs to one process. Nothing here divides the host's chips
    among children, so each child would try to open all of them: the
    first wins and the rest fail or hang at backend start-up — the same
    happens to every child of a parent that already touched JAX. One
    process drives all local chips (`fleet.init` + a mesh). Children whose
    environment pins `JAX_PLATFORMS=cpu` need no chip and are let
    through. The chips are counted from PCI (what jax itself does before
    it picks a backend), so this initialises nothing."""
    if nprocs <= 1:
        return
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu":
        return
    from jax._src import hardware_utils
    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if chips:
        raise RuntimeError(
            f"{what}: refusing to start {nprocs} processes on a host with "
            f"{chips} TPU chip(s). A chip belongs to one process and the "
            f"children are not given separate chips, so they would fail "
            f"or hang at backend start-up. Drive all local chips from ONE "
            f"process (fleet.init(is_collective=True) lays a mesh over "
            f"them), or pin JAX_PLATFORMS=cpu for a CPU-only "
            f"multi-process run.")


class ParallelEnv:
    def __init__(self):
        self.rank = get_rank()
        self.world_size = get_world_size()
        self.device_id = 0
        self.current_endpoint = os.environ.get(
            "PADDLE_CURRENT_ENDPOINT", "127.0.0.1:6170")
        self.trainer_endpoints = os.environ.get(
            "PADDLE_TRAINER_ENDPOINTS", self.current_endpoint).split(",")

    @property
    def nranks(self):
        return self.world_size

    @property
    def local_rank(self):
        return self.rank


def get_rank(group=None) -> int:
    try:
        return jax.process_index()
    except Exception:
        return 0


def get_world_size(group=None) -> int:
    try:
        return jax.process_count()
    except Exception:
        return 1


def is_initialized() -> bool:
    return _initialized


def init_parallel_env(strategy=None) -> ParallelEnv:
    """Multi-host bootstrap. Single-host (this environment): builds the
    default all-devices mesh and returns. Multi-host: initializes the jax
    distributed runtime from PADDLE_* / JAX coordinator env vars, after
    which jax.devices() spans all hosts and meshes lay over ICI+DCN."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or \
        os.environ.get("PADDLE_MASTER") or None
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM",
                               os.environ.get("JAX_NUM_PROCESSES", "1")))
    pid = int(os.environ.get("PADDLE_TRAINER_ID",
                             os.environ.get("JAX_PROCESS_ID", "0")))
    if coord and nproc > 1:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    # elastic mode: start this rank's heartbeat against the master's KV
    # server (reference ElasticManager; see fleet/elastic.py)
    kv_ep = os.environ.get("PADDLE_ELASTIC_KV")
    if kv_ep:
        from .fleet.elastic import HeartbeatClient
        HeartbeatClient(kv_ep, rank=pid).start()
    from ..parallel.mesh import create_mesh, get_mesh
    if get_mesh() is None:
        create_mesh({"dp": len(jax.devices())})
    _initialized = True
    return ParallelEnv()
