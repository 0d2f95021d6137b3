"""Pipeline-parallel invariance: pp2×mp2×dp2 loss == single-device loss.

Reference pattern (SURVEY.md §4-hybrid): launch procs, assert loss curves
match the single-process run. Here: one SPMD program on the 8-device CPU
mesh vs the plain eager forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel import fleet
from paddle_tpu.parallel.pipeline import make_pipeline_train_step
from paddle_tpu.parallel.strategy import DistributedStrategy
from paddle_tpu.parallel.topology import set_hybrid_communicate_group


@pytest.fixture
def pp_fleet():
    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
                        "sharding_degree": 1}
    s.pipeline = True
    s.pipeline_configs.accumulate_steps = 4
    f = fleet.init(is_collective=True, strategy=s)
    yield f, s
    set_hybrid_communicate_group(None)


@pytest.mark.slow  # tier-1 budget (PR 3 offset): sibling coverage stays tier-1
def test_pipeline_matches_single_device(pp_fleet):
    f, s = pp_fleet
    cfg = LlamaConfig.tiny()
    cfg.tie_word_embeddings = False
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)

    rng = np.random.RandomState(0)
    B, seq = 8, 16
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, seq + 1)))
    x, y = ids[:, :-1], ids[:, 1:]

    ref_loss = float(model.loss(model(x), y))

    opt = AdamW(learning_rate=1e-3)
    step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
    state, opt_state = init_fn()
    state, opt_state, loss0 = step_fn(state, opt_state,
                                      {"input": x, "labels": y})
    np.testing.assert_allclose(float(loss0), ref_loss, rtol=2e-5)

    for _ in range(4):
        state, opt_state, loss = step_fn(state, opt_state,
                                         {"input": x, "labels": y})
    assert float(loss) < float(loss0)


@pytest.mark.slow
def test_pipeline_with_recompute_matches(pp_fleet):
    f, s = pp_fleet
    s.recompute = True
    cfg = LlamaConfig.tiny()
    cfg.tie_word_embeddings = False
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 17)))
    x, y = ids[:, :-1], ids[:, 1:]
    ref_loss = float(model.loss(model(x), y))
    opt = AdamW(learning_rate=1e-3)
    step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
    state, opt_state = init_fn()
    _, _, loss0 = step_fn(state, opt_state, {"input": x, "labels": y})
    np.testing.assert_allclose(float(loss0), ref_loss, rtol=2e-5)


def test_pipeline_tied_embeddings_matches(pp_fleet):
    f, s = pp_fleet
    cfg = LlamaConfig.tiny()
    cfg.tie_word_embeddings = True
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 17)))
    x, y = ids[:, :-1], ids[:, 1:]
    ref_loss = float(model.loss(model(x), y))
    opt = AdamW(learning_rate=1e-3)
    step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
    state, opt_state = init_fn()
    _, _, loss0 = step_fn(state, opt_state, {"input": x, "labels": y})
    np.testing.assert_allclose(float(loss0), ref_loss, rtol=2e-5)


@pytest.mark.slow
def test_pipeline_zero2_matches_single_device():
    """North-star combination (BASELINE.json metric): mp2 × pp2 × ZeRO
    sharding stage-2 — first-step loss equals the single-device loss, and
    training still descends with grads/opt-state sharded over the
    'sharding' axis."""
    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
                        "sharding_degree": 2}
    s.pipeline = True
    s.pipeline_configs.accumulate_steps = 4
    s.sharding = True
    s.sharding_configs.stage = 2
    fleet.init(is_collective=True, strategy=s)
    try:
        cfg = LlamaConfig.tiny()
        cfg.tie_word_embeddings = False
        paddle_tpu.seed(0)
        model = LlamaForCausalLM(cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 17)))
        x, y = ids[:, :-1], ids[:, 1:]
        ref_loss = float(model.loss(model(x), y))
        opt = AdamW(learning_rate=1e-3)
        step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
        state, opt_state = init_fn()
        # moments really live sharded: some opt leaf's PartitionSpec names
        # the axis (str(leaf.sharding) would match any NamedSharding on
        # this mesh — the spec is the actual placement)
        sharded_leaves = [
            v for tree in opt_state.values() if isinstance(tree, dict)
            for v in tree.values()
            if "sharding" in str(getattr(getattr(v, "sharding", None),
                                         "spec", ""))]
        assert sharded_leaves, "no optimizer-state leaf sharded over 'sharding'"
        state, opt_state, loss0 = step_fn(state, opt_state,
                                          {"input": x, "labels": y})
        np.testing.assert_allclose(float(loss0), ref_loss, rtol=2e-5)
        for _ in range(4):
            state, opt_state, loss = step_fn(state, opt_state,
                                             {"input": x, "labels": y})
        assert float(loss) < float(loss0)
    finally:
        set_hybrid_communicate_group(None)


# ---- schedule engine (1F1B / interleaved) ---------------------------------

def test_schedule_tables_replay():
    """Replay the static tables: every F reads its producer's activation,
    every B reads its own stash and the consumer stage's gradient."""
    from paddle_tpu.parallel.pipeline_schedules import build_schedule_tables

    for (S, v, M) in [(2, 1, 4), (4, 1, 8), (2, 2, 4), (4, 2, 8), (3, 1, 5)]:
        tb = build_schedule_tables(S, v, M)
        VS = v * S
        f_buf = [[None] * tb.fwd_ring for _ in range(S)]
        g_buf = [[None] * tb.grad_ring for _ in range(S)]
        stash = [[None] * tb.stash_ring for _ in range(S)]
        h_wire = [None] * S
        g_wire = [None] * S
        f_done, b_done = set(), set()
        for t in range(tb.n_ticks):
            for s in range(S):
                if tb.f_wr[t, s] >= 0:
                    f_buf[s][tb.f_wr[t, s]] = h_wire[s]
                if tb.b_gwr[t, s] >= 0:
                    g_buf[s][tb.b_gwr[t, s]] = g_wire[s]
            h_out, g_out = [None] * S, [None] * S
            for s in range(S):
                if tb.f_active[t, s]:
                    c, m = tb.f_c[t, s], tb.f_m[t, s]
                    V = c * S + s
                    if tb.f_src[t, s] == -2:
                        assert V == 0
                        x = ("h", -1, m)
                    else:
                        x = f_buf[s][tb.f_src[t, s]]
                        assert x == ("h", V - 1, m)
                    stash[s][tb.f_stash[t, s]] = (V, m)
                    h_out[s] = ("h", V, m)
                    f_done.add((V, m))
                if tb.b_active[t, s]:
                    c, m = tb.b_c[t, s], tb.b_m[t, s]
                    V = c * S + s
                    assert stash[s][tb.b_stash[t, s]] == (V, m)
                    if tb.b_gsrc[t, s] == -2:
                        assert V == VS - 1
                    else:
                        assert g_buf[s][tb.b_gsrc[t, s]] == ("g", V + 1, m)
                    g_out[s] = ("g", V, m)
                    b_done.add((V, m))
            h_wire = [h_out[(s - 1) % S] for s in range(S)]
            g_wire = [g_out[(s + 1) % S] for s in range(S)]
        assert len(f_done) == VS * M and len(b_done) == VS * M
        # 1F1B memory signature: stash depth is O(S·v), never O(M)
        assert tb.stash_ring <= 2 * (VS - 1) + 1


def _run_schedule(schedule, vpp=1, acc=4, n_layers=2, steps=2):
    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
                        "sharding_degree": 1}
    s.pipeline = True
    s.pipeline_configs.accumulate_steps = acc
    s.pipeline_configs.schedule_mode = schedule
    s.pipeline_configs.virtual_pp_degree = vpp
    fleet.init(is_collective=True, strategy=s)
    try:
        cfg = LlamaConfig.tiny()
        cfg.tie_word_embeddings = False
        cfg.num_layers = n_layers
        paddle_tpu.seed(0)
        model = LlamaForCausalLM(cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 17)))
        x, y = ids[:, :-1], ids[:, 1:]
        ref = float(model.loss(model(x), y))
        opt = AdamW(learning_rate=1e-3)
        step_fn, init_fn = make_pipeline_train_step(model, opt, strategy=s)
        state, opt_state = init_fn()
        losses = []
        for _ in range(steps):
            state, opt_state, l = step_fn(state, opt_state,
                                          {"input": x, "labels": y})
            losses.append(float(l))
        return ref, losses, {k: np.asarray(v) for k, v in state.items()}
    finally:
        set_hybrid_communicate_group(None)


def test_1f1b_matches_gpipe_and_single_device():
    ref_g, losses_g, st_g = _run_schedule("FThenB")
    ref_f, losses_f, st_f = _run_schedule("1F1B")
    np.testing.assert_allclose(losses_g[0], ref_g, rtol=2e-5)
    np.testing.assert_allclose(losses_f[0], ref_f, rtol=2e-5)
    np.testing.assert_allclose(losses_f, losses_g, rtol=1e-4)
    for k in st_g:
        np.testing.assert_allclose(st_f[k], st_g[k], rtol=5e-4, atol=2e-4,
                                   err_msg=k)


def test_interleaved_matches_gpipe():
    S, v = 2, 2
    ref_g, losses_g, st_g = _run_schedule("FThenB", n_layers=4)
    ref_i, losses_i, st_i = _run_schedule("1F1B", vpp=v, n_layers=4)
    np.testing.assert_allclose(ref_i, ref_g, rtol=1e-6)
    np.testing.assert_allclose(losses_i[0], ref_i, rtol=2e-5)
    np.testing.assert_allclose(losses_i, losses_g, rtol=1e-4)
    for k in st_g:
        a, b = st_i[k], st_g[k]
        if k.startswith("blocks."):
            # interleaved [s, c, j] holds layer (c*S+s)*pc+j; gpipe [s, j]
            # holds layer s*per+j — compare per layer
            pc = a.shape[2]
            a = a.transpose(1, 0, *range(2, a.ndim)).reshape(
                (S * v * pc,) + a.shape[3:])
            b = b.reshape((S * b.shape[1],) + b.shape[2:])
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=2e-4, err_msg=k)


def test_unknown_schedule_raises(pp_fleet):
    f, s = pp_fleet
    s.pipeline_configs.schedule_mode = "zigzag"
    cfg = LlamaConfig.tiny()
    cfg.tie_word_embeddings = False
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(cfg)
    with pytest.raises(ValueError, match="schedule_mode"):
        make_pipeline_train_step(model, AdamW(learning_rate=1e-3), strategy=s)


@pytest.mark.slow
def test_lazy_guard_aot_matches_eager():
    """LazyGuard (meta-init) models: no parameter buffer is allocated,
    the pipeline AOT lower() path produces byte-identical memory
    accounting to the eager-built twin, and execution paths fail loudly."""
    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
                        "sharding_degree": 1}
    s.pipeline = True
    s.pipeline_configs.accumulate_steps = 2
    fleet.init(is_collective=True, strategy=s)
    try:
        cfg = LlamaConfig.tiny()
        cfg.tie_word_embeddings = False
        paddle_tpu.seed(0)
        eager = LlamaForCausalLM(cfg)
        with paddle_tpu.LazyGuard():
            lazy = LlamaForCausalLM(cfg).bfloat16()
        assert all(isinstance(p.value, jax.ShapeDtypeStruct)
                   for _, p in lazy.named_parameters())
        assert lazy.num_params() == eager.num_params()

        opt = AdamW(learning_rate=1e-3)
        step_e, _ = make_pipeline_train_step(eager.bfloat16(), opt,
                                             strategy=s)
        step_l, init_l = make_pipeline_train_step(lazy, opt, strategy=s)
        ma_e = step_e.lower(4, 16).compile().memory_analysis()
        ma_l = step_l.lower(4, 16).compile().memory_analysis()
        assert ma_l.argument_size_in_bytes == ma_e.argument_size_in_bytes
        assert ma_l.temp_size_in_bytes == ma_e.temp_size_in_bytes
        with pytest.raises(RuntimeError, match="LazyGuard"):
            init_l()
    finally:
        set_hybrid_communicate_group(None)


@pytest.mark.slow
def test_lazy_guard_generic_path_lower_and_guard():
    """The non-pipeline make_train_step also serves LazyGuard models:
    lower() works (== eager accounting), init_fn raises the explicit
    meta-init error."""
    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                        "sharding_degree": 2}
    s.sharding = True
    s.sharding_configs.stage = 2
    fleet.init(is_collective=True, strategy=s)
    try:
        cfg = LlamaConfig.tiny()
        paddle_tpu.seed(0)
        eager = LlamaForCausalLM(cfg)
        with paddle_tpu.LazyGuard():
            lazy = LlamaForCausalLM(cfg)
        loss_fn = lambda out, b: eager.loss(out, b["labels"])
        step_e, _ = fleet.make_train_step(eager, AdamW(learning_rate=1e-3),
                                          loss_fn, strategy=s)
        step_l, init_l = fleet.make_train_step(
            lazy, AdamW(learning_rate=1e-3),
            lambda out, b: lazy.loss(out, b["labels"]), strategy=s)
        ma_e = step_e.lower(8, 16).compile().memory_analysis()
        ma_l = step_l.lower(8, 16).compile().memory_analysis()
        assert ma_l.argument_size_in_bytes == ma_e.argument_size_in_bytes
        with pytest.raises(RuntimeError, match="LazyGuard"):
            init_l()
    finally:
        set_hybrid_communicate_group(None)
