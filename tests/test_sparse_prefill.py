"""``sparse_prefill_attn`` (``ops/sparse_paged.py``), interpreted on the
CPU, and the ``jnp`` path against the reference under the per-token
mask: the kernel holds a score tile as (keys, queries), reads the block
selection down the sublanes and adds the causal edge itself, at the two
block sizes of the models that run it (MiniCPM-SALA's 64, MSA's 128).

float32 inputs are multiplied at full precision inside the kernel, so
the two differ by summation order only; bf16 inputs by bf16's rounding
of a probability.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.flags import set_flags
from paddle_tpu.ops import msa_index as msa
from paddle_tpu.ops import sparse_paged as spg


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def _selection(key, n, C, G, NB, t, bs, share):
    """Each query reads block 0 and its own block (the forced blocks of
    both models) and a ``share`` of the others."""
    blocks = jax.random.bernoulli(key, share, (n, C, G, NB))
    own = jax.nn.one_hot(t // bs, NB, dtype=bool)[:, :, None]
    return (blocks | own).at[..., 0].set(True)


# G, bs, C, S, kv_len, share of the other blocks, dtype
@pytest.mark.parametrize("G, bs, C, S, kv_len, share, dtype", [
    # the two models' groups and blocks, a chunk of 256 at 256 .. 511
    (2, 64, 256, 512, 512, 0.5, jnp.bfloat16),
    (4, 128, 256, 512, 512, 0.5, jnp.bfloat16),
    # 10 blocks of 64: not a whole lane tile; one key tile of 640
    (2, 64, 256, 640, 640, 0.4, jnp.float32),
    # two key tiles of 512
    (4, 128, 256, 1024, 1024, 0.4, jnp.bfloat16),
    # a chunk at c0 = 744 whose last key tile (512 .. 1023) is partial,
    # and rows past kv_len that no query may read
    (2, 64, 256, 1536, 1000, 0.4, jnp.float32),
    # queries at 1,024 .. 1,535 that read block 0 and their own block
    # only: in key tile 0 the one visible block is block 0
    (2, 64, 512, 1536, 1536, 0.0, jnp.float32),
    # the first chunk: every query's keys lie in its own tile's past
    (4, 128, 512, 2048, 512, 0.4, jnp.float32),
], ids=["sala", "msa", "sala-partial-lane-tile", "msa-two-key-tiles",
        "c0-partial-key-tile", "block0-alone-in-tile0", "first-chunk"])
def test_the_kernel_against_the_reference(monkeypatch, G, bs, C, S, kv_len,
                                          share, dtype):
    n, H, d = 1, 2 * G, 128
    ks = jax.random.split(jax.random.key(S + C + G), 3)
    q = jax.random.normal(ks[0], (n, C, H, d)).astype(dtype)
    kv = jax.random.normal(ks[1], (n, S, 2 * G * d)).astype(dtype)
    t = jnp.broadcast_to(kv_len - C + jnp.arange(C), (n, C))
    blocks = _selection(ks[2], n, C, G, S // bs, t, bs, share)
    want = spg.sparse_prefill_attention_reference(
        q, kv, spg.prefill_token_mask(blocks, t, S, bs), groups=G)
    # off the kernel: the reference under the token mask, made here
    got_jnp = spg.sparse_prefill_attention(q, kv, blocks, jnp.int32(kv_len),
                                           groups=G, block_size=bs)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=1e-6)
    calls = []
    kernel = spg._sparse_prefill_pallas
    monkeypatch.setattr(spg, "_sparse_prefill_pallas",
                        lambda *a, **k: calls.append(k) or kernel(*a, **k))
    set_flags({"FLAGS_pallas_interpret": True})
    got = spg.sparse_prefill_attention(q, kv, blocks, jnp.int32(kv_len),
                                       groups=G, block_size=bs)
    assert calls and calls[0]["interpret"]
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("sp", [
    spg.SparseConfig(dense_len=2304, window_size=128, topk=4),
    msa.msa_config()], ids=["sala", "msa"])
def test_every_query_reads_block_0(sp):
    """What the kernel relies on: key tile 0 gives every query a finite
    maximum, so a masked score weighs exactly 0 and ``p`` needs no mask
    of its own. Queries past ``dense_len`` and under it alike."""
    NB = 40
    t = jnp.arange(0, NB * sp.block_size, 37)
    R = jax.random.uniform(jax.random.key(1), (t.shape[0], 2, NB))
    assert bool(spg.select_mask(R, t, sp)[..., 0].all())
