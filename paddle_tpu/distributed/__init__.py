"""paddle.distributed (reference `python/paddle/distributed/`)."""
from . import collective, fleet, sharding, transpiler
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig
from .sharding import group_sharded_parallel, save_group_sharded_model
from .collective import (ReduceOp, all_gather, all_reduce, alltoall, barrier,
                         broadcast, get_group, new_group, recv, reduce,
                         reduce_scatter, scatter, send, shard_ctx, split,
                         wait)
from .env import (ParallelEnv, get_rank, get_world_size, init_parallel_env,
                  is_initialized, refuse_processes_per_chip)
from .parallel import DataParallel
from .tensor_parallel import (ColumnParallelLinear, RowParallelLinear,
                              VocabParallelEmbedding)


def _spawn_target(func, args, env):
    import os
    os.environ.update(env)
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """reference `distributed/spawn.py:276`. TPU note: SPMD spans local
    chips from one process, so nprocs>1 is only for multi-host-style
    testing on the CPU; it starts python processes wired with the
    PADDLE_* env, and refuses on a host with TPU chips
    (`env.refuse_processes_per_chip`). `func` must be importable (the
    children are started with the spawn method)."""
    import multiprocessing as mp
    if nprocs in (-1, 0, 1):
        func(*args)
        return
    refuse_processes_per_chip(nprocs, "distributed.spawn")
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {"PADDLE_TRAINER_ID": str(rank),
               "PADDLE_TRAINERS_NUM": str(nprocs)}
        p = ctx.Process(target=_spawn_target, args=(func, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
