"""Parallel environment bring-up (≈ paddle.distributed.init_parallel_env).

Reference call stack (SURVEY.md §3.2): TCPStore rendezvous on rank0 →
ProcessGroupNCCL per group. TPU-native: `jax.distributed.initialize` performs
the DCN rendezvous (coordinator ≈ TCPStore) and the ICI/DCN fabric replaces
NCCL communicators. Env vars mirror the reference launcher contract
(PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_MASTER) with JAX-native
fallbacks, so `python -m paddle_tpu.parallel.launch` scripts port over.
"""

import os

import jax

_initialized = [False]


def init_parallel_env(coordinator_address=None, num_processes=None,
                      process_id=None):
    """Multi-host bring-up. Single-process (possibly multi-device) needs no init."""
    if _initialized[0]:
        return ParallelEnv()
    coord = coordinator_address or os.environ.get("PADDLE_MASTER") or \
        os.environ.get("COORDINATOR_ADDRESS")
    nproc = num_processes or _env_int("PADDLE_TRAINERS_NUM") or _env_int("NUM_PROCESSES")
    pid = process_id if process_id is not None else \
        (_env_int("PADDLE_TRAINER_ID") if "PADDLE_TRAINER_ID" in os.environ
         else _env_int("PROCESS_ID"))
    if coord and nproc and nproc > 1:
        # CPU cross-process collectives need the gloo implementation
        # (the CPU-simulated analog of the reference's Gloo backend,
        # SURVEY.md §2.5); harmless when the backend is TPU.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid or 0)
    _initialized[0] = True
    return ParallelEnv()


def _env_int(name):
    v = os.environ.get(name)
    return int(v) if v is not None else None


def is_initialized():
    return _initialized[0]


def get_rank(group=None):
    if group is not None:
        return group.rank
    return jax.process_index()


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    return jax.process_count()


def device_count():
    return jax.device_count()


class ParallelEnv:
    """Reference `paddle.distributed.ParallelEnv` parity object."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def dev_id(self):
        return 0
