"""2-process CPU driver for the multi-process collective leg.

Run by tests/test_multiprocess.py in a subprocess. Exercises the real
cross-process path the reference's ProcessGroup backend provides
(SURVEY.md §2.5): `launch.spawn` → per-rank `init_parallel_env` →
`jax.distributed.initialize` (TCPStore-analog rendezvous) → eager
collectives over two OS processes with one CPU device each.

Not named test_* on purpose — pytest must not collect it in-process.
"""

import os
import socket
import sys


def _pin_cpu_devices(n):
    """Must run before the worker's first backend query."""
    import jax
    jax.config.update("jax_num_cpu_devices", n)


def _worker(rank, port):
    # pin the platform BEFORE any backend query
    import jax
    jax.config.update("jax_platforms", "cpu")
    _pin_cpu_devices(1)

    os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
    from paddle_tpu.parallel import collective as coll
    from paddle_tpu.parallel import env as penv

    penv.init_parallel_env()
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 2, jax.device_count()
    assert penv.get_rank() == rank

    import jax.numpy as jnp

    r = coll.all_reduce(jnp.asarray([float(rank + 1)]))
    assert r.tolist() == [3.0], r

    m = coll.all_reduce(jnp.asarray([float(rank)]), op=coll.ReduceOp.MAX)
    assert m.tolist() == [1.0], m

    g = coll.all_gather(jnp.asarray([float(rank)]))
    assert g.tolist() == [[0.0], [1.0]], g

    lst = coll.all_gather([], jnp.asarray([float(rank)]))
    assert [t.tolist() for t in lst] == [[0.0], [1.0]], lst

    b = coll.broadcast(jnp.asarray([rank * 5.0]), src=1)
    assert b.tolist() == [5.0], b

    rs = coll.reduce_scatter(jnp.arange(4.0) + rank)
    expected = [1.0, 3.0] if rank == 0 else [5.0, 7.0]
    assert rs.tolist() == expected, rs

    a2a = coll.alltoall(
        jnp.asarray([[rank, rank], [rank + 10, rank + 10]], jnp.float32))
    exp = ([[0.0, 0.0], [1.0, 1.0]] if rank == 0
           else [[10.0, 10.0], [11.0, 11.0]])
    assert a2a.tolist() == exp, a2a

    sc = coll.scatter(jnp.zeros(1),
                      tensor_list=[jnp.asarray([10.0]), jnp.asarray([20.0])]
                      if rank == 0 else None, src=0)
    assert sc.tolist() == ([10.0] if rank == 0 else [20.0]), sc

    # eager p2p (round 3: KV-store backed — no longer NotImplementedError)
    if rank == 0:
        coll.send(jnp.asarray([2.5]), dst=1)
    else:
        got = coll.recv(jnp.zeros(1), src=0)
        assert got.tolist() == [2.5], got

    coll.barrier()
    print(f"rank{rank} MP_OK", flush=True)


def _pipeline_worker(rank, port, expected_loss):
    """True multi-host pipeline: the pp2 1F1B train step as ONE
    multi-controller SPMD program over a global mesh spanning two OS
    processes (stage 0 on rank 0's device, stage 1 on rank 1's) — the
    TPU-native answer to the reference's cross-host NCCL pipeline."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    _pin_cpu_devices(1)

    os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
    from paddle_tpu.parallel import env as penv

    penv.init_parallel_env()
    assert jax.process_count() == 2 and jax.device_count() == 2

    import numpy as np
    import paddle_tpu
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.pipeline import make_pipeline_train_step
    from paddle_tpu.parallel.strategy import DistributedStrategy

    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
                        "sharding_degree": 1}
    s.pipeline = True
    s.pipeline_configs.accumulate_steps = 2
    fleet.init(is_collective=True, strategy=s)

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3)
    step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
    state, opt_state = init_fn()

    ids = np.random.RandomState(0).randint(0, 256, (2, 17))
    batch = {"input": ids[:, :-1], "labels": ids[:, 1:]}
    state, opt_state, loss = step_fn(state, opt_state, batch)
    loss = float(loss)
    assert np.isfinite(loss), loss
    if expected_loss is not None:
        assert abs(loss - expected_loss) < 1e-3, (loss, expected_loss)
    print(f"rank{rank} PIPELINE_MP_OK loss={loss:.5f}", flush=True)


def _subgroup_worker(rank, port):
    """Eager ProcessGroup completeness leg (VERDICT r2 #6): 3 processes ×
    2 CPU devices each (multi-device hosts ride the KV exchange, not the
    1-device-per-process allgather fast path), a size-2 OFFSET subgroup
    {0, 2} created via new_group (src args are GLOBAL ranks — rank 2 is
    group-local 1), a non-member process that never enters, and eager
    send/recv."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    _pin_cpu_devices(2)

    os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
    from paddle_tpu.parallel import collective as coll
    from paddle_tpu.parallel import env as penv

    penv.init_parallel_env()
    assert jax.process_count() == 3, jax.process_count()
    assert jax.local_device_count() == 2      # multi-device host
    assert jax.device_count() == 6

    import jax.numpy as jnp

    # world collectives on a 2-device-per-process host (KV path)
    r = coll.all_reduce(jnp.asarray([float(rank + 1)]))
    assert r.tolist() == [6.0], r
    ag = coll.all_gather(jnp.asarray([float(rank * 7)]))
    assert ag.tolist() == [[0.0], [7.0], [14.0]], ag

    # offset size-2 subgroup {0, 2}: global src ranks, local positions
    sub = coll.new_group(ranks=[0, 2], name="pair")
    if rank in (0, 2):
        assert sub.pg_size == 2 and sub.pg_rank == (0 if rank == 0 else 1)
        sr = coll.all_reduce(jnp.asarray([2.0 + rank]), group=sub)
        assert sr.tolist() == [6.0], sr          # (2+0) + (2+2)
        sb = coll.broadcast(jnp.asarray([rank * 3.0]), src=2, group=sub)
        assert sb.tolist() == [6.0], sb          # GLOBAL src=2 holds 6.0
        sc = coll.reduce_scatter(jnp.arange(4.0) + rank, group=sub)
        expected = [2.0, 4.0] if rank == 0 else [6.0, 8.0]
        assert sc.tolist() == expected, sc
        coll.barrier(group=sub)
    else:
        assert not sub.is_member()
        try:
            coll.all_reduce(jnp.zeros(1), group=sub)
        except RuntimeError as e:
            assert "not a member" in str(e)
        else:
            raise AssertionError("non-member collective must raise")

    # eager p2p over the coordination service (global ranks 0 <-> 2)
    if rank == 0:
        coll.send(jnp.asarray([41.5]), dst=2)
        got = coll.recv(jnp.zeros(1), src=2)
        assert got.tolist() == [13.25], got
    elif rank == 2:
        got = coll.recv(jnp.zeros(1), src=0)
        assert got.tolist() == [41.5], got
        coll.send(jnp.asarray([13.25]), dst=0)

    print(f"rank{rank} SUBGROUP_MP_OK", flush=True)


def _hybrid4_worker(rank, port, expected_loss):
    """4-process leg (VERDICT r3 #8): the hybrid dp2 × pp2 1F1B train step
    as ONE multi-controller SPMD program over FOUR OS processes (one CPU
    device each: pp stages across process pairs, dp within) — must
    reproduce the single-process 4-device loss."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    _pin_cpu_devices(1)

    os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
    from paddle_tpu.parallel import env as penv

    penv.init_parallel_env()
    assert jax.process_count() == 4 and jax.device_count() == 4

    import numpy as np
    import paddle_tpu
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.pipeline import make_pipeline_train_step
    from paddle_tpu.parallel.strategy import DistributedStrategy

    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 1, "pp_degree": 2,
                        "sharding_degree": 1}
    s.pipeline = True
    s.pipeline_configs.accumulate_steps = 2
    fleet.init(is_collective=True, strategy=s)

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    opt = AdamW(learning_rate=1e-3)
    step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
    state, opt_state = init_fn()

    ids = np.random.RandomState(0).randint(0, 256, (4, 17))
    batch = {"input": ids[:, :-1], "labels": ids[:, 1:]}
    state, opt_state, loss = step_fn(state, opt_state, batch)
    loss = float(loss)
    assert np.isfinite(loss), loss
    if expected_loss is not None:
        assert abs(loss - expected_loss) < 1e-3, (loss, expected_loss)

    # storeless elastic: membership registry over THIS job's own
    # coordination-service KV (no shared dir)
    from paddle_tpu.parallel.elastic import (CoordinationServiceStore,
                                             ElasticManager)
    from paddle_tpu.parallel import collective as coll
    store = CoordinationServiceStore.from_jax(prefix="hb_test")
    # generous TTL (timeout) so cross-process barriers on a loaded CI host
    # can't expire a live rank between its register() and our alive() read
    mgr = ElasticManager(store, rank=rank, world_size=4,
                         heartbeat_interval=0.5, timeout=60.0).start()
    coll.barrier()
    assert mgr.alive() == {0, 1, 2, 3}, mgr.alive()
    coll.barrier()
    if rank == 3:
        mgr.stop(deregister=True)     # simulated orderly host loss
    coll.barrier()
    assert mgr.alive() == {0, 1, 2}, mgr.alive()
    assert mgr.dead() == {3}, mgr.dead()
    coll.barrier()
    if rank != 3:
        mgr.stop(deregister=True)
    print(f"rank{rank} HYBRID4_MP_OK loss={loss:.5f}", flush=True)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    from paddle_tpu.parallel import launch

    which = sys.argv[1] if len(sys.argv) > 1 else "collectives"
    if which == "collectives":
        launch.spawn(_worker, args=(_free_port(),), nprocs=2)
    elif which == "pipeline":
        expected = float(sys.argv[2]) if len(sys.argv) > 2 else None
        launch.spawn(_pipeline_worker, args=(_free_port(), expected),
                     nprocs=2)
    elif which == "subgroup":
        launch.spawn(_subgroup_worker, args=(_free_port(),), nprocs=3)
    elif which == "hybrid4":
        expected = float(sys.argv[2]) if len(sys.argv) > 2 else None
        launch.spawn(_hybrid4_worker, args=(_free_port(), expected),
                     nprocs=4)
    else:
        raise SystemExit(f"unknown driver mode {which!r}")
    print("DRIVER_OK", flush=True)


if __name__ == "__main__":
    main()
