"""MiniCPM-SALA: lightning linear-attention layers and InfLLM-v2
block-sparse attention layers in one decoder, MiniCPM's muP scalings.

``mixer_types[l]`` names layer l's mixer; the layers differ by KIND:

* ``"lightning-attn"``: H heads of d, no grouping, RMSNorm over d on
  each head of q and k, rope, a decayed ``d x d`` float32 state a head
  (:mod:`paddle_tpu.ops.lightning_attention`), an RMSNorm over all ``H
  d`` outputs and a sigmoid gate before ``W_o``. Such a layer has NO
  cache of keys and values: a request's state is ``H d d`` floats
  however long it is.
* ``"minicpm4"``: GQA, RMSNorm over d on each head of q and k, no rope,
  a sigmoid gate before ``W_o``. A query with more than ``dense_len``
  tokens before it reads the first block, a window, and the ``topk``
  blocks its own scores against compressed keys pick
  (:mod:`paddle_tpu.ops.sparse_paged`); one selection a KV group a
  token, in prefill too. Such a layer caches ``[k | v]`` rows and, 16x
  shorter, the compressed keys.

Model: ``h = scale_emb E[ids]``; ``h += a Mixer(RMSNorm(h))``, ``h += a
FFN(RMSNorm(h))``, ``a = scale_depth / sqrt(scale_depth_layers)`` (the
published depth, also in a cut); ``logits = W_head(RMSNorm(h) /
(hidden_size / dim_model_base))``.

A prefill of s positions runs in chunks of ``prefill_chunk`` inside ONE
program (a ``lax.scan`` over chunks, the layers unrolled inside it): the
lightning states, the keys and values and the compressed keys are the
carry, so no ``(s, intermediate_size)`` tensor is ever whole. A row's
lightning state stops at its TRUE length (``positions + 1``): the pad of
a wave neither adds to it nor decays it.

The mathematics is in module-level functions over ``{name: array}``
weights: the chunked forward and the paged decode step read the same
leaves of one state.
"""

import dataclasses
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu import nn
from paddle_tpu.models.llama import CausalLMBase
from paddle_tpu.models.xing4 import _proj, _sub, _swiglu
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops import lightning_attention as la
from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.ops import sparse_paged as spg
from paddle_tpu.ops.rms_norm import rms_norm
from paddle_tpu.profiler.parts import part

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# what ``decode_step`` counts, in the order it returns them: 64-token
# blocks the sparse layers read and could have read (summed over active
# rows, groups and layers), active rows at or under dense_len (x sparse
# layers), active rows (x lightning layers)
STEP_COUNTERS = ("sparse_blocks_read", "sparse_blocks_visible",
                 "sparse_dense_rows", "lightning_rows")

_PUBLISHED_MIXERS = (
    [SPARSE] + [LIGHTNING] * 8 + [SPARSE] + [LIGHTNING] * 6 + [SPARSE] * 2
    + [LIGHTNING] * 4 + [SPARSE] + [LIGHTNING] * 6 + [SPARSE] * 3)


@dataclasses.dataclass
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_layers: int = 32
    mixer_types: Optional[List[str]] = None     # None: the published 32
    num_heads: int = 32                         # the sparse layers' queries
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    scale_depth_layers: Optional[int] = None    # None: num_layers
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    sparse_config: Dict = dataclasses.field(
        default_factory=lambda: dataclasses.asdict(spg.SparseConfig()))
    prefill_chunk: int = 2048

    def __post_init__(self):
        if self.mixer_types is None:
            self.mixer_types = list(_PUBLISHED_MIXERS)
        if len(self.mixer_types) != self.num_layers or set(
                self.mixer_types) - {LIGHTNING, SPARSE}:
            raise ValueError(
                f"mixer_types must name {self.num_layers} mixers of "
                f"{LIGHTNING!r} / {SPARSE!r}, got {self.mixer_types}")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("grouped lightning heads are not implemented "
                             f"(lightning_nkv {self.lightning_nkv} != "
                             f"lightning_nh {self.lightning_nh})")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {self.num_heads} is not a multiple "
                             f"of num_kv_heads {self.num_kv_heads}")
        if self.scale_depth_layers is None:
            self.scale_depth_layers = self.num_layers
        self.sparse = spg.SparseConfig(**self.sparse_config)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.scale_depth_layers)

    def layers_of(self, kind: str) -> List[int]:
        return [i for i, k in enumerate(self.mixer_types) if k == kind]

    @classmethod
    def tiny(cls, vocab_size=256, **over):
        """Every mechanism at toy widths: 1 sparse + 2 lightning + 1
        sparse layer, heads of 16, a selection that bites past 64
        tokens."""
        kw = dict(vocab_size=vocab_size, hidden_size=64,
                  intermediate_size=96, num_layers=4,
                  mixer_types=[SPARSE, LIGHTNING, LIGHTNING, SPARSE],
                  num_heads=4, num_kv_heads=2, head_dim=16, lightning_nh=4,
                  lightning_nkv=4, lightning_head_dim=16,
                  scale_depth_layers=8, dim_model_base=16,
                  max_position_embeddings=1024, prefill_chunk=32,
                  sparse_config=dict(kernel_size=8, kernel_stride=4,
                                     block_size=8, topk=2, window_size=16,
                                     init_blocks=1, dense_len=64))
        kw.update(over)
        return cls(**kw)


# ----------------------------------------------------------- the functions
def _lin(w: Dict, name: str, x):
    return jnp.matmul(x, w[name + ".weight"])


def _heads(w: Dict, name: str, x, heads: int, d: int, eps: float,
           norm: bool = True):
    """x (..., C) -> ``W_name x`` as (..., heads, d), each head normed."""
    y = _lin(w, name + "_proj", x).reshape(*x.shape[:-1], heads, d)
    return rms_norm(y, w[name + "_norm.weight"], eps) if norm else y


def _gated_out(w: Dict, x, o):
    """``W_o(o * sigmoid(W_g x))``."""
    return _lin(w, "o_proj", o.astype(x.dtype)
                * jax.nn.sigmoid(_lin(w, "o_gate", x)))


def _rope(cfg: MiniCPMSALAConfig, positions):
    """(cos, sin), each ``positions.shape + (lightning_head_dim,)``."""
    return rope_ops.rope_cos_sin(None, cfg.lightning_head_dim,
                                 base=cfg.rope_theta, position_ids=positions)


def lightning_qkv(w: Dict, cfg: MiniCPMSALAConfig, x, cos, sin):
    """x (b, s, C) -> q, k after norm and rope, v: each (b, s, H, d)."""
    H, d, eps = cfg.lightning_nh, cfg.lightning_head_dim, cfg.rms_norm_eps
    q = rope_ops.apply_rotary_pos_emb(_heads(w, "q", x, H, d, eps), cos, sin)
    k = rope_ops.apply_rotary_pos_emb(_heads(w, "k", x, H, d, eps), cos, sin)
    return q, k, _heads(w, "v", x, H, d, eps, norm=False)


def lightning_out(w: Dict, cfg: MiniCPMSALAConfig, x, o):
    """o (..., H, d) float32 -> the mixer's output (..., C)."""
    o = rms_norm(o.reshape(*o.shape[:-2], -1),
                 w["o_norm.weight"].astype(jnp.float32), cfg.rms_norm_eps)
    return _gated_out(w, x, o)


def sparse_qkv(w: Dict, cfg: MiniCPMSALAConfig, x):
    """x (..., C) -> q (..., H, d), k and v (..., G d): q and k normed a
    head, no rope."""
    H, G, d, eps = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                    cfg.rms_norm_eps)
    k = _heads(w, "k", x, G, d, eps)
    return (_heads(w, "q", x, H, d, eps), k.reshape(*k.shape[:-2], G * d),
            _lin(w, "v_proj", x))


def prefill_select(q, kc, c0, sp: spg.SparseConfig, NB: int, rows: int = 256):
    """The blocks every query of a chunk at positions ``c0 ..`` reads: q
    (n, C, H, d), kc (n, J, G, d) -> (n, C, G, NB) bool. A chunk that
    ends at or under ``dense_len`` reads every block up to each query's
    own, which is what :func:`~paddle_tpu.ops.sparse_paged.select_mask`
    would answer for it: no score is computed there. Past it ``rows``
    queries at a time, so that the (rows, H, J) scores stay small."""
    n, C = q.shape[:2]
    G = kc.shape[2]
    t = jnp.broadcast_to(c0 + jnp.arange(C), (n, C))

    def dense():
        return jnp.broadcast_to(spg.visible_blocks(t, sp, NB)[:, :, None],
                                (n, C, G, NB))

    def slab(args):
        qb, tb = args
        return spg.select_mask(
            spg.block_scores(spg.stage1(qb, kc, tb, sp), sp, NB), tb, sp)

    def select():
        if C <= rows or C % rows:
            return slab((q, t))
        out = lax.map(slab, (
            jnp.moveaxis(q.reshape(n, C // rows, rows, *q.shape[2:]), 1, 0),
            jnp.moveaxis(t.reshape(n, C // rows, rows), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(n, C, *out.shape[3:])

    return lax.cond(c0 + C <= sp.dense_len, dense, select)


def sparse_chunk(w: Dict, cfg: MiniCPMSALAConfig, x, kv, ck, tail, c0):
    """One sparse layer's mixer over a chunk: x (n, C, C_h) at positions
    ``c0 ..``; kv (n, S, 2 G d), ck (n, S / stride + lead, G d) (``lead``
    dummy rows, then row j at ``lead + j``) and tail (n, lead stride, G
    d), the keys just before the chunk -> (y, kv', ck', tail', the
    (n, C, G, NB) blocks the queries read)."""
    sp = cfg.sparse
    n, C, _ = x.shape
    G, d = cfg.num_kv_heads, cfg.head_dim
    S = kv.shape[1]
    lead = tail.shape[1] // sp.kernel_stride
    with part("attn_in"):
        q, k, v = sparse_qkv(w, cfg, x)
    with part("attn"):
        kv = lax.dynamic_update_slice_in_dim(
            kv, jnp.concatenate([k, v], -1).astype(kv.dtype), c0, axis=1)
        ext = jnp.concatenate([tail, k.astype(tail.dtype)], 1)
        # the windows that END in this chunk: rows c0 / stride - lead ..
        ck = lax.dynamic_update_slice_in_dim(
            ck, spg.compress(ext, sp).astype(ck.dtype),
            c0 // sp.kernel_stride, axis=1)
    with part("select"):
        blocks = prefill_select(q, ck[:, lead:].reshape(n, -1, G, d), c0, sp,
                                -(-S // sp.block_size))
    with part("attn"):
        o = spg.sparse_prefill_attention(q, kv, blocks, c0 + C, groups=G,
                                         block_size=sp.block_size)
    with part("attn_out"):
        y = _gated_out(w, x, o.reshape(n, C, -1))
    return y, kv, ck, ext[:, ext.shape[1] - tail.shape[1]:], blocks


def init_cache(cfg: MiniCPMSALAConfig, n: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """The prefill's carry: ``kv`` (sparse layers, n, len, 2 G d), ``ck``
    (sparse layers, n, len / stride + lead, G d), ``tail`` (the keys
    before the next chunk) and ``state`` (lightning layers, n, H, d, d)
    float32."""
    sp = cfg.sparse
    if max_len % sp.kernel_stride:
        raise ValueError(f"cache length {max_len} is not a multiple of "
                         f"kernel_stride {sp.kernel_stride}")
    Lp, Ll = len(cfg.layers_of(SPARSE)), len(cfg.layers_of(LIGHTNING))
    gd = cfg.num_kv_heads * cfg.head_dim
    lead = sp.kernel_size // sp.kernel_stride - 1
    H, d = cfg.lightning_nh, cfg.lightning_head_dim
    return {"kv": jnp.zeros((Lp, n, max_len, 2 * gd), dtype),
            "ck": jnp.zeros((Lp, n, max_len // sp.kernel_stride + lead, gd),
                            dtype),
            "tail": jnp.zeros((Lp, n, lead * sp.kernel_stride, gd), dtype),
            "state": jnp.zeros((Ll, n, H, d, d), jnp.float32)}


def hidden_forward(w: Dict, cfg: MiniCPMSALAConfig, ids, cache: Dict,
                   true_len=None, positions=None, return_blocks=False):
    """ids (n, s) from position 0, in chunks -> (the residual before the
    final norm: (n, s, C), or (n, C) at ``positions`` (n,); the cache
    after the s positions, its lightning states after each row's
    ``true_len`` (n,) tokens; with ``return_blocks`` also the blocks
    every query read, (sparse layers, n, s, G, NB))."""
    n, s = ids.shape
    C = la.chunk_size(s, cfg.prefill_chunk)
    a = cfg.residual_scale
    eps = cfg.rms_norm_eps
    kinds = cfg.mixer_types
    true_len = (jnp.full((n,), s, jnp.int32) if true_len is None
                else true_len.astype(jnp.int32))
    emb = w["model.embed_tokens.weight"]

    def chunk(carry, i):
        cache, picked = carry
        c0 = i * C
        with part("embed"):
            tok = lax.dynamic_slice_in_dim(ids, c0, C, axis=1)
            x = (cfg.scale_emb * jnp.take(emb, tok, axis=0)).astype(emb.dtype)
        with part("attn_in"):
            cos, sin = _rope(cfg, c0 + jnp.arange(C))
        nvalid = jnp.clip(true_len - c0, 0, C)
        kv, ck, tail, state = (cache[k] for k in
                               ("kv", "ck", "tail", "state"))
        li = lp = 0
        blocks = []
        for l, kind in enumerate(kinds):
            lw = _sub(w, f"model.layers.{l}.")
            mw = _sub(lw, "self_attn.")
            with part("norm"):
                xn = rms_norm(x, lw["input_layernorm.weight"], eps)
            if kind == LIGHTNING:
                with part("attn_in"):
                    q, k, v = lightning_qkv(mw, cfg, xn, cos, sin)
                with part("state"):
                    o, st = la.lightning_prefill(q, k, v, state[li], nvalid)
                    state = state.at[li].set(st)
                with part("attn_out"):
                    y = lightning_out(mw, cfg, xn, o)
                li += 1
            else:
                y, kv_l, ck_l, tail_l, blk = sparse_chunk(
                    mw, cfg, xn, kv[lp], ck[lp], tail[lp], c0)
                with part("attn"):
                    kv, ck, tail = (kv.at[lp].set(kv_l), ck.at[lp].set(ck_l),
                                    tail.at[lp].set(tail_l))
                blocks.append(blk)
                lp += 1
            with part("attn_out"):
                x = x + (a * y).astype(x.dtype)
            with part("norm"):
                xn = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
            with part("ffn"):
                x = x + (a * _swiglu(_sub(lw, "mlp."), xn)).astype(x.dtype)
        cache = {"kv": kv, "ck": ck, "tail": tail, "state": state}
        out = {}
        if positions is None:
            out["x"] = x
        else:
            with part("head"):
                at = jnp.clip(positions - c0, 0, C - 1)
                here = (positions >= c0) & (positions < c0 + C)
                row = jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]
                picked = jnp.where(here[:, None], row, picked)
        if return_blocks:
            out["blocks"] = jnp.stack(blocks)
        return (cache, picked), out

    picked = jnp.zeros((n, emb.shape[1]), emb.dtype)
    (cache, picked), out = lax.scan(chunk, (cache, picked),
                                    jnp.arange(s // C))
    merge = lambda o, axis: jnp.moveaxis(o, 0, axis).reshape(
        *o.shape[1:axis + 1], s, *o.shape[axis + 2:])
    h = picked if positions is not None else merge(out["x"], 1)
    if return_blocks:
        return h, cache, merge(out["blocks"], 2)
    return h, cache


def head_forward(w: Dict, cfg: MiniCPMSALAConfig, h):
    with part("head"):
        hn = rms_norm(h, w["model.norm.weight"], cfg.rms_norm_eps)
        return jnp.matmul(hn / (cfg.hidden_size / cfg.dim_model_base),
                          w["lm_head.weight"])


def decode_step(w: Dict, cfg: MiniCPMSALAConfig, x, pool, tables, positions,
                state):
    """One token a row through every block: x (b, C) embeddings; pool
    ``(kv (L_s, NB, BT, 2 G d), ck (L_s, NB, BT / stride, G d))`` over the
    sparse layers; tables (b, MB); positions (b,); state ``{"lightning":
    (L_l, b, H, d, d)}``. A row whose table starts at the scratch block
    is idle: it appends nothing and its state stays. -> (the residual
    (b, C), pool, state, int32 (4,): :data:`STEP_COUNTERS`)."""
    sp, a, eps = cfg.sparse, cfg.residual_scale, cfg.rms_norm_eps
    kv_pool, ck_pool = pool
    S = state["lightning"]
    active = tables[:, 0] != 0
    with part("attn_in"):
        cos, sin = _rope(cfg, positions[:, None])           # (b, 1, d)
    tallies = jnp.zeros(3, jnp.int32)
    li = lp = 0
    for l, kind in enumerate(cfg.mixer_types):
        lw = _sub(w, f"model.layers.{l}.")
        mw = _sub(lw, "self_attn.")
        with part("norm"):
            xn = rms_norm(x, lw["input_layernorm.weight"], eps)
        if kind == LIGHTNING:
            with part("attn_in"):
                q, k, v = lightning_qkv(mw, cfg, xn[:, None], cos, sin)
            with part("state"):
                o, S = la.lightning_decode(q[:, 0], k[:, 0], v[:, 0], S,
                                           active, layer=li)
            with part("attn_out"):
                y = lightning_out(mw, cfg, xn, o)
            li += 1
        else:
            with part("attn_in"):
                q, k, v = sparse_qkv(mw, cfg, xn)
            with part("attn"):
                kv_pool, ck_pool = spg.append_kv(
                    kv_pool, ck_pool, tables, positions, k, v, active,
                    layer=lp, sp=sp)
            with part("select"):
                blocks, c = spg.sparse_select(q, ck_pool, tables, positions,
                                              active, layer=lp, sp=sp)
                tallies = tallies + c
            with part("attn"):
                o = spg.sparse_paged_decode(q, kv_pool, tables, positions,
                                            blocks, layer=lp, sp=sp)
            with part("attn_out"):
                y = _gated_out(mw, xn, o.reshape(o.shape[0], -1))
            lp += 1
        with part("attn_out"):
            x = x + (a * y).astype(x.dtype)
        with part("norm"):
            xn = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
        with part("ffn"):
            x = x + (a * _swiglu(_sub(lw, "mlp."), xn)).astype(x.dtype)
    with part("state"):
        rows = active.sum(dtype=jnp.int32) * li
    return (x, (kv_pool, ck_pool), {"lightning": S},
            jnp.concatenate([tallies, rows[None]]))


# -------------------------------------------------------------- the layers
class SALAMixer(nn.Layer):
    """Either mixer's parameters: projections, per-head q and k norms, a
    gate; a lightning mixer also norms its output."""

    def __init__(self, cfg: MiniCPMSALAConfig, kind: str):
        super().__init__()
        C, std, eps = cfg.hidden_size, cfg.initializer_range, cfg.rms_norm_eps
        if kind == LIGHTNING:
            H = G = cfg.lightning_nh
            d = cfg.lightning_head_dim
        else:
            H, G, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        self.q_proj = _proj(C, H * d, std)
        self.k_proj = _proj(C, G * d, std)
        self.v_proj = _proj(C, G * d, std)
        self.o_gate = _proj(C, H * d, std)
        self.o_proj = _proj(H * d, C, std)
        self.q_norm = nn.RMSNorm(d, epsilon=eps)
        self.k_norm = nn.RMSNorm(d, epsilon=eps)
        if kind == LIGHTNING:
            self.o_norm = nn.RMSNorm(H * d, epsilon=eps)


class SALAMLP(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        C, F, std = (cfg.hidden_size, cfg.intermediate_size,
                     cfg.initializer_range)
        self.gate_proj = _proj(C, F, std)
        self.up_proj = _proj(C, F, std)
        self.down_proj = _proj(F, C, std)


class SALADecoderLayer(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig, kind: str):
        super().__init__()
        C, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(C, epsilon=eps)
        self.self_attn = SALAMixer(cfg, kind)
        self.post_attention_layernorm = nn.RMSNorm(C, epsilon=eps)
        self.mlp = SALAMLP(cfg)


class MiniCPMSALAModel(nn.Layer):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=init.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([SALADecoderLayer(cfg, kind)
                                    for kind in cfg.mixer_types])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)


class MiniCPMSALAForCausalLM(CausalLMBase):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        if cfg.tie_word_embeddings:
            raise ValueError("MiniCPMSALAForCausalLM has an untied output "
                             "head")
        self.cfg = cfg
        self.model = MiniCPMSALAModel(cfg)
        self.lm_head = _proj(cfg.hidden_size, cfg.vocab_size,
                             cfg.initializer_range)
        from paddle_tpu.parallel import mp_layers as mp
        self.loss_fn = mp.ParallelCrossEntropy()

    def _weights(self) -> Dict:
        return {n: p.value for n, p in self.named_parameters()}

    def init_cache(self, batch_size, max_len, dtype=jnp.bfloat16):
        return init_cache(self.cfg, batch_size, max_len, dtype)

    def loss(self, logits, labels):
        return self.loss_fn(logits, labels, reduction="mean")

    def forward(self, input_ids, attn_mask=None, cache=None, start_pos=0,
                positions: Optional[jax.Array] = None):
        """Logits (b, s, vocab); with ``positions`` (b,) only each row's
        logits at that position, (b, vocab), and each row's lightning
        state is the one after ``positions + 1`` tokens: the rest of the
        row is pad. With ``cache`` (from ``init_cache``, at least s long)
        also the filled cache. A forward starts at position 0: a
        recurrent state cannot be entered midway."""
        del attn_mask       # causal; serving pads on the right
        if not (isinstance(start_pos, int) and start_pos == 0):
            raise ValueError("MiniCPMSALAForCausalLM prefills from position "
                             "0 only (no state is kept at a block's edge)")
        w, cfg = self._weights(), self.cfg
        b, s = input_ids.shape
        own = cache if cache is not None else init_cache(
            cfg, b, s, w["model.embed_tokens.weight"].dtype)
        h, own = hidden_forward(
            w, cfg, input_ids, own, positions=positions,
            true_len=None if positions is None else positions + 1)
        logits = head_forward(w, cfg, h)
        return logits if cache is None else (logits, own)

    def fused_decode_plan(self, state, probe=False):
        """What ``serving.ServingEngine`` asks of a model (docs/SERVING.md
        §Architectures the engine takes): ``arch`` ``"sala"``, pool rows
        for the sparse layers only (``pool_layers``), their compressed
        keys as a second paged leaf (``pool_aux``), the lightning states
        as a fixed-size leaf a slot (``slot_state``), ``to_lanes`` and
        ``to_state`` from the prefill's cache, and a step that carries
        the state."""
        if "model.layers.0.self_attn.o_gate.weight" not in state:
            return None     # a quantized or otherwise foreign state
        cfg = self.cfg
        sp = cfg.sparse
        gd = cfg.num_kv_heads * cfg.head_dim
        n_sparse = len(cfg.layers_of(SPARSE))
        n_light = len(cfg.layers_of(LIGHTNING))
        lead = sp.kernel_size // sp.kernel_stride - 1

        def prefill_calls(R: int, s_pad: int) -> Dict:
            C = max(la.chunk_size(s_pad, cfg.prefill_chunk), 1)
            chunks = s_pad // C
            # the chunks that end past dense_len: the others select nothing
            selecting = chunks - min(sp.dense_len // C, chunks)
            return {"lightning_calls": n_light * chunks,
                    "sparse_calls": n_sparse * chunks,
                    "select_calls": n_sparse * selecting}

        meta = {
            "arch": "sala", "cache_lanes": 2 * gd, "pool_layers": n_sparse,
            "pool_aux": {"stride": sp.kernel_stride, "lanes": gd},
            "slot_state": {"lightning": (
                (n_light, cfg.lightning_nh, cfg.lightning_head_dim,
                 cfg.lightning_head_dim), jnp.float32)},
            "to_lanes": lambda cache: (cache["kv"], cache["ck"][:, :, lead:]),
            "to_state": lambda cache: {"lightning": cache["state"]},
            "prefill_calls": prefill_calls,
            "step_counters": STEP_COUNTERS}
        if probe:
            return meta

        def embed(tok, pos):
            del pos
            e = state["model.embed_tokens.weight"]
            with part("embed"):
                return (cfg.scale_emb
                        * jnp.take(e, tok, axis=0)).astype(e.dtype)

        def step(x, pool, tables, positions, slot_state):
            return decode_step(state, cfg, x, pool, tables, positions,
                               slot_state)

        def head(x):
            return head_forward(state, cfg, x)

        return dict(meta, embed=embed, step=step, head=head)
