"""Aux subsystems: gradient merge, nan/inf watcher, profiler metrics, LR
schedulers, grad clip, collectives veneer, topology arithmetic, flags."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import fleet
from paddle_tpu.parallel.strategy import DistributedStrategy
from paddle_tpu.parallel.topology import (
    CommunicateTopology,
    set_hybrid_communicate_group,
)


@pytest.mark.slow
def test_gradient_merge_matches_full_batch():
    """k-step accumulation over a homogeneous batch == full-batch step."""
    cfg = LlamaConfig.tiny()
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 17)))
    batch = {"input": ids[:, :-1], "labels": ids[:, 1:]}

    def run(k):
        s = DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                            "sharding_degree": 1}
        if k > 1:
            s.gradient_merge = True
            s.gradient_merge_configs["k_steps"] = k
        fleet.init(is_collective=True, strategy=s,
                   devices=jax.devices()[:1])
        try:
            opt = AdamW(learning_rate=1e-3)
            step_fn, init_fn = fleet.make_train_step(
                model, opt, lambda lg, b: model.loss(lg, b["labels"]),
                strategy=s)
            st, ost = init_fn()
            st, ost, loss = step_fn(st, ost, batch)
            return float(loss), st
        finally:
            set_hybrid_communicate_group(None)

    loss1, st1 = run(1)
    loss2, st2 = run(2)
    # same data per microbatch row split; losses are means → close
    np.testing.assert_allclose(loss2, loss1, rtol=1e-4)
    w1 = np.asarray(st1["model.embed_tokens.weight"])
    w2 = np.asarray(st2["model.embed_tokens.weight"])
    np.testing.assert_allclose(w2, w1, rtol=1e-3, atol=1e-5)


def test_nan_inf_watcher():
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.utils.nan_inf import check_numerics, tree_nonfinite_count
    tree = {"a": jnp.asarray([1.0, jnp.inf]), "b": jnp.ones(3)}
    assert int(tree_nonfinite_count(tree)) == 1
    set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError):
            check_numerics(tree, "grads")
        assert check_numerics({"a": jnp.ones(2)}, "ok")
    finally:
        set_flags({"FLAGS_check_nan_inf": False})


def test_step_timer_and_metrics(tmp_path):
    import json
    import time
    from paddle_tpu.profiler import MetricsLogger, StepTimer, model_flops_per_token
    t = StepTimer(model_flops_per_token(1000), warmup=0)
    for _ in range(3):
        with t:
            time.sleep(0.01)
    assert t.mean_step_time() >= 0.01
    assert t.tokens_per_sec(100) > 0
    assert t.mfu(100, peak=1e6) is not None
    assert t.mfu(100) is None   # CPU: unknown device kind, no guessed peak
    ml = MetricsLogger(str(tmp_path / "m.jsonl"))
    ml.log(step=1, loss=2.5)
    rec = json.loads(open(tmp_path / "m.jsonl").read().strip())
    assert rec["loss"] == 2.5 and "ts" in rec


def test_lr_schedulers():
    from paddle_tpu.optimizer import lr as lr_mod
    warm = lr_mod.LinearWarmup(lr_mod.CosineAnnealingDecay(0.1, 100),
                               warmup_steps=10, start_lr=0.0, end_lr=0.1)
    v0 = float(warm.value(0))
    v5 = float(warm.value(5))
    v10 = float(warm.value(10))
    assert v0 < v5 < v10 <= 0.1 + 1e-6
    cos = lr_mod.CosineAnnealingDecay(0.1, 100)
    assert float(cos.value(100)) < float(cos.value(0))


def test_grad_clip_global_norm():
    from paddle_tpu.optimizer import ClipGradByGlobalNorm
    clip = ClipGradByGlobalNorm(1.0)
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((4,), 10.0)}
    out = clip(g)
    total = float(jnp.sqrt(sum(jnp.sum(v ** 2) for v in out.values())))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)
    g_small = {"a": jnp.full((2,), 0.01)}
    out2 = clip(g_small)
    np.testing.assert_allclose(np.asarray(out2["a"]), 0.01, rtol=1e-6)


def test_collective_veneers():
    from paddle_tpu.parallel import collective as C
    g = C.new_group(list(range(8)))
    x = jnp.arange(8.0).reshape(8, 1)
    red = C.all_reduce(x, group=g)
    np.testing.assert_allclose(np.asarray(red), np.full((8, 1), 28.0))
    b = C.broadcast(x, src=3, group=g)
    np.testing.assert_allclose(np.asarray(b), np.full((8, 1), 3.0))
    a2a = C.alltoall(jnp.arange(16.0).reshape(4, 4), group=C.new_group([0, 1, 2, 3]))
    np.testing.assert_allclose(np.asarray(a2a),
                               np.arange(16.0).reshape(4, 4).T)


def test_topology_arithmetic():
    topo = CommunicateTopology(["dp", "pp", "mp"], [2, 2, 2])
    assert topo.world_size() == 8
    assert topo.get_rank(dp=1, pp=0, mp=1) == 5
    assert topo.get_coord(5) == (1, 0, 1)
    groups = topo.get_comm_list("mp")
    assert [0, 1] in groups and len(groups) == 4


def test_flags_roundtrip():
    from paddle_tpu.core.flags import flag, set_flags
    set_flags({"FLAGS_use_pallas_kernels": False})
    assert flag("FLAGS_use_pallas_kernels") is False
    set_flags({"FLAGS_use_pallas_kernels": True})
    assert flag("FLAGS_use_pallas_kernels") is True
    with pytest.raises(KeyError):
        set_flags({"FLAGS_definitely_unknown": 1})


@pytest.mark.slow
def test_profiler_summary_and_chrome_trace(tmp_path):
    """summary() parses real xplane protos; export produces catapult JSON."""
    import json

    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import Profiler, export_chrome_tracing

    out_dir = str(tmp_path / "chrome")
    prof = Profiler(log_dir=str(tmp_path / "trace"),
                    on_trace_ready=export_chrome_tracing(out_dir))
    prof.start()
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((128, 128))
    for _ in range(2):
        f(x).block_until_ready()
    prof.stop()

    s = prof.summary()
    assert "Total(ms)" in s and "Calls" in s
    assert len(s.splitlines()) > 3  # real rows, not a pointer string

    trace_path = tmp_path / "chrome" / "trace.json"
    assert trace_path.exists()
    trace = json.loads(trace_path.read_text())
    evs = trace["traceEvents"]
    assert any(e.get("ph") == "X" and e.get("dur", 0) > 0 for e in evs)
    assert any(e.get("ph") == "M" for e in evs)
