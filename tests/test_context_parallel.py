"""Ring attention / Ulysses invariance vs full attention on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.ops.flash_attention import _xla_attention
from paddle_tpu.parallel.context_parallel import context_parallel_attention
from paddle_tpu.parallel import fleet
from paddle_tpu.parallel.strategy import DistributedStrategy
from paddle_tpu.parallel.topology import set_hybrid_communicate_group


@pytest.fixture
def sep_fleet():
    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                        "sharding_degree": 1, "sep_degree": 2}
    f = fleet.init(is_collective=True, strategy=s)
    yield f
    set_hybrid_communicate_group(None)


def _qkv(b=2, s=16, h=4, kvh=2, d=8, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_context_parallel_matches_full(sep_fleet, mode, causal):
    q, k, v = _qkv()
    ref = _xla_attention(q, k, v, is_causal=causal, dropout_p=0.0)
    mesh = sep_fleet.mesh

    out = jax.jit(lambda q, k, v: context_parallel_attention(
        q, k, v, mesh=mesh, mode=mode, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_context_parallel_grads_match(sep_fleet, mode):
    q, k, v = _qkv(seed=3)
    mesh = sep_fleet.mesh

    def loss_cp(q, k, v):
        return jnp.sum(context_parallel_attention(
            q, k, v, mesh=mesh, mode=mode, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, is_causal=True,
                                      dropout_p=0.0) ** 2)

    g_cp = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_cp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_llama_with_ring_attention_matches_dense(sep_fleet):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.layer import functional_call

    cfg = LlamaConfig.tiny()
    paddle_tpu.seed(0)
    dense = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 17)))
    x, y = ids[:, :-1], ids[:, 1:]
    ref_loss = float(dense.loss(dense(x), y))

    cfg_cp = LlamaConfig.tiny()
    cfg_cp.context_parallel = "ring"
    cp_model = LlamaForCausalLM(cfg_cp)
    cp_model.set_state_dict(dense.state_dict())

    def loss_of(state):
        return cp_model.loss(functional_call(cp_model, state, x), y)

    got = float(jax.jit(loss_of)(cp_model.trainable_state()))
    np.testing.assert_allclose(got, ref_loss, rtol=2e-5)


def test_no_mesh_degenerates_to_full_attention():
    q, k, v = _qkv(seed=5)
    out = context_parallel_attention(q, k, v, mesh=None, mode="ring")
    ref = _xla_attention(q, k, v, is_causal=True, dropout_p=0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
