"""paddle.distributed.spawn analog (reference: distributed/spawn.py).

On TPU the normal model is one process per host (jax handles all local chips), so
spawn is for CPU-mesh tests; it starts `nprocs` fresh interpreters with the
reference's PADDLE_* env contract. A chip belongs to one process at a time, so
spawn refuses what cannot work there: more than one worker on a host that
exposes TPU chips (each would claim them all), and any worker at all once this
process has itself brought up an accelerator backend (it holds the chips).
"""
from __future__ import annotations

import multiprocessing as mp
import os

from .launch import require_one_process_per_chip_host


def _wrapper(func, rank, nprocs, base_port, args):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    endpoints = ",".join(f"127.0.0.1:{base_port + i}" for i in range(nprocs))
    os.environ["PADDLE_TRAINER_ENDPOINTS"] = endpoints
    os.environ["PADDLE_CURRENT_ENDPOINT"] = f"127.0.0.1:{base_port + rank}"
    func(*args)


def _parent_holds_accelerator() -> bool:
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    import jax
    return jax.default_backend() != "cpu"


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    require_one_process_per_chip_host(nprocs)
    if _parent_holds_accelerator():
        raise RuntimeError(
            "spawn: this process has already initialised an accelerator "
            "backend and holds its chips; a spawned worker that needs them "
            "would fail or hang. Spawn before touching JAX, or drive all "
            "local chips from this one process")
    base_port = int(options.get("started_port", 35000))
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_wrapper,
                        args=(func, rank, nprocs, base_port, args),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(f"spawned process exited with {p.exitcode}")
    return procs
