"""MiniMax-M3: MSA block-sparse attention in every layer, routed experts
behind a sigmoid router, held whole or as ONE chip's share of an
expert-parallel layer.

* **Norms** are Gemma's, ``x / rms(x) * (1 + w)``: the layers' input and
  post-attention norms, the final norm, and the per-head norms of q, k
  and the indexer's queries (one weight of ``head_dim`` a kind, shared by
  its heads).
* **Attention**: GQA, ``num_attention_heads`` query heads in
  ``num_key_value_heads`` groups of d = ``head_dim``; q and k normed a
  head, then rope on their first ``rotary_dim`` lanes (half-split pairs,
  base ``rope_theta``), the rest as they are; no output gate.
* **MSA** (:mod:`paddle_tpu.ops.msa_index`): a group's
  ``msa_index_heads`` indexer heads ``u = rope(norm(W_I x))`` score the
  max-pooled keys of the complete ``msa_block_size``-token blocks before
  the query's own; the query reads block 0, its own block and the
  ``msa_topk`` best others (every block while there are no more), with
  :mod:`paddle_tpu.ops.sparse_paged`'s two attention kernels. The cache
  holds ``[k | v]`` rows and, one row a block, the pooled keys, which
  are kept in float32 (:func:`index_queries`).
* **FFN**: ``moe_layer_freq[l]`` 0 is a dense ``swigluoai`` FFN of
  ``dense_intermediate_size``; 1 is ``num_experts_per_tok`` of
  ``router_experts`` routed experts (``p = sigmoid(W_r x)``, chosen by
  ``p + bias``, weights the chosen ``p`` normalised to 1 and times
  ``routed_scaling_factor``) plus ``n_shared_experts`` shared experts,
  every one of width ``intermediate_size``. ``swigluoai(g, u) = min(g,
  L) sigmoid(alpha min(g, L)) (clip(u, -L, L) + 1)``.
* **A share** (as ``models.deepseek_v2``): ``num_local_experts`` experts
  are held, the router's ids ``expert_offset ..``; the router keeps its
  full width and its picks, a pick on another chip costs nothing here,
  and nothing stands in for the other chips. ``vocab_size`` is the rows
  of the embedding and the head that are held.

A prefill of s positions runs in chunks of ``prefill_chunk`` inside ONE
program: a ``lax.scan`` over GROUPS of chunks, the layers unrolled inside
it, the ``[k | v]`` rows and the pooled keys as the carry. In a group,
each layer's attention runs chunk by chunk in order (a scan of
``msa_chunk``), then the group's rows go through the routed experts in
ONE call of the prefill expert kernel, which streams every held expert
once a row tile. A group is as many chunks as keep a held expert's mean
rows (``prefill_chunk k / router_experts`` a chunk) and three standard
deviations of a random router's spread inside one tile
(:func:`chunks_a_call`: 3 chunks of 2,048 at the published widths, 1
where a chunk fills the tile); a smaller last group runs once after the
scan, at its own size (:func:`prefill_groups`). A row's pooled keys
stop at its TRUE length, so the pad of a wave never enters a block's
max. The mathematics is in module-level functions over ``{name:
array}`` weights: the chunked forward and the paged decode step read the
same leaves of one state.
"""

import dataclasses
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu import nn
from paddle_tpu.models.llama import CausalLMBase
from paddle_tpu.models.minicpm_sala import _lin
from paddle_tpu.models.xing4 import Xing4MLP, _proj, _sub
from paddle_tpu.nn import initializer as init
from paddle_tpu.nn.layers.moe import GroupedSwiGLUExperts, sigmoid_topk_routing
from paddle_tpu.ops import lightning_attention as la
from paddle_tpu.ops import moe_grouped
from paddle_tpu.ops import msa_index as msa
from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.ops import sparse_paged as spg
from paddle_tpu.ops.rms_norm import rms_norm
from paddle_tpu.profiler.parts import part

_HI = lax.Precision.HIGHEST
# what ``decode_step`` counts, in the order it returns them: MSA blocks
# read and visible (summed over active rows, groups and layers), active
# rows at or under the MSA dense length (x layers), a constant 0 (no
# linear-attention layers: the name the sparse reader shares with the
# hybrid plan), then the expert counters of ``models.deepseek_v2``
STEP_COUNTERS = ("sparse_blocks_read", "sparse_blocks_visible",
                 "sparse_dense_rows", "lightning_rows", "moe_layer_steps",
                 "moe_experts_touched", "moe_rows_max", "moe_rows",
                 "moe_picks")


@dataclasses.dataclass
class MiniMaxM3Config:
    vocab_size: int = 200064                # the rows held here
    hidden_size: int = 6144
    intermediate_size: int = 3072           # one routed (or shared) expert
    dense_intermediate_size: int = 12288
    shared_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    moe_layer_freq: Optional[List[int]] = None  # None: 3 dense, then MoE
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 5000000.0
    rms_norm_eps: float = 1e-6
    msa_index_heads: int = 4
    msa_block_size: int = 128
    msa_topk: int = 16
    num_local_experts: int = 128            # the experts held here
    router_experts: Optional[int] = None    # the router's width
    expert_offset: int = 0                  # the first held expert's id
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    swiglu_alpha: float = 1.702
    swiglu_limit: float = 7.0
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    prefill_chunk: int = 2048

    def __post_init__(self):
        if self.moe_layer_freq is None:
            self.moe_layer_freq = [0] * 3 + [1] * (self.num_hidden_layers - 3)
        self.moe_layer_freq = [int(f) for f in self.moe_layer_freq]
        if self.router_experts is None:
            self.router_experts = self.num_local_experts
        if (len(self.moe_layer_freq) != self.num_hidden_layers
                or set(self.moe_layer_freq) - {0, 1}):
            raise ValueError(
                f"moe_layer_freq must give 0 or 1 for each of the "
                f"{self.num_hidden_layers} layers, got {self.moe_layer_freq}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} is not a "
                f"multiple of num_key_value_heads {self.num_key_value_heads}")
        if not (0 <= self.expert_offset and self.expert_offset
                + self.num_local_experts <= self.router_experts):
            raise ValueError(
                f"held experts {self.expert_offset}..+"
                f"{self.num_local_experts} lie outside the router's "
                f"{self.router_experts}")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} must be even "
                             f"and at most head_dim {self.head_dim}")
        self.msa = msa.msa_config(self.msa_block_size, self.msa_topk)

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def act(self):
        return ("swigluoai", float(self.swiglu_alpha),
                float(self.swiglu_limit))

    def is_moe(self, layer: int) -> bool:
        return bool(self.moe_layer_freq[layer])

    @classmethod
    def tiny(cls, vocab_size=256, **over):
        """Every mechanism at toy widths: 1 dense + 2 expert layers, 16
        of 32 experts held, blocks of 8 and a top 2, so that the
        selection bites past 32 tokens."""
        kw = dict(vocab_size=vocab_size, hidden_size=64, intermediate_size=32,
                  dense_intermediate_size=96, shared_intermediate_size=32,
                  num_hidden_layers=3, moe_layer_freq=[0, 1, 1],
                  num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                  rotary_dim=8, rope_theta=10000.0, msa_index_heads=2,
                  msa_block_size=8, msa_topk=2, num_local_experts=16,
                  router_experts=32, num_experts_per_tok=4,
                  max_position_embeddings=1024, prefill_chunk=32)
        kw.update(over)
        return cls(**kw)


# ----------------------------------------------------------- the functions
def _norm(x, w, eps: float):
    """Gemma's RMSNorm: ``x / rms(x) * (1 + w)``."""
    return rms_norm(x, (1.0 + w.astype(jnp.float32)).astype(x.dtype), eps)


def _rope(cfg: MiniMaxM3Config, positions):
    """(cos, sin), each ``positions.shape + (rotary_dim,)``."""
    return rope_ops.rope_cos_sin(None, cfg.rotary_dim, base=cfg.rope_theta,
                                 position_ids=positions)


def _heads(w: Dict, cfg: MiniMaxM3Config, name: str, x, heads: int, cos,
           sin):
    """x (..., C) -> ``rope(norm(W_name x))`` as (..., heads, d) float32:
    the products accumulated and kept in float32 (no rounding of the
    projection's output), the norm a head, rope on the first
    ``rotary_dim`` lanes."""
    d, r = cfg.head_dim, cfg.rotary_dim
    y = jnp.matmul(x, w[name + "_proj.weight"],
                   preferred_element_type=jnp.float32)
    y = _norm(y.reshape(*x.shape[:-1], heads, d), w[name + "_norm.weight"],
              cfg.rms_norm_eps)
    return jnp.concatenate(
        [rope_ops.apply_rotary_pos_emb(y[..., :r], cos, sin), y[..., r:]], -1)


def attn_inputs(w: Dict, cfg: MiniMaxM3Config, x, cos, sin):
    """x (..., C) normed -> q (..., H, d) in ``x.dtype``, k (..., G d)
    float32 (the pooled keys are taken of it before the cache rounds
    it), v (..., G d)."""
    G, d = cfg.num_key_value_heads, cfg.head_dim
    q = _heads(w, cfg, "q", x, cfg.num_attention_heads, cos, sin)
    k = _heads(w, cfg, "k", x, G, cos, sin)
    return (q.astype(x.dtype), k.reshape(*k.shape[:-2], G * d),
            _lin(w, "v_proj", x))


def index_queries(w: Dict, cfg: MiniMaxM3Config, x, cos, sin):
    """x (..., C) normed -> the indexer's queries (..., G, I, d) float32.
    With random weights the blocks' scores stand closer than a bf16
    rounding of the keys or the queries resolves (``PERF.md`` §6, PR
    38), so the indexer's inputs are kept in float32, as a router's
    scores are."""
    G, I = cfg.num_key_value_heads, cfg.msa_index_heads
    u = _heads(w, cfg, "index", x, G * I, cos, sin)
    return u.reshape(*u.shape[:-2], G, I, cfg.head_dim)


def _mlp(w: Dict, cfg: MiniMaxM3Config, x):
    """``W_down swigluoai(W_gate x, W_up x)``, the activation in
    float32."""
    f32 = jnp.float32
    a = moe_grouped.swigluoai(_lin(w, "gate_proj", x).astype(f32),
                              _lin(w, "up_proj", x).astype(f32),
                              *cfg.act[1:])
    return _lin(w, "down_proj", a.astype(x.dtype))


def route(w: Dict, cfg: MiniMaxM3Config, x):
    """x (T, C) -> (rows of the held experts (T, k), ``num_local_experts``
    where the pick lies on another chip; weights (T, k) float32). The
    scores in float32 at full matmul precision, over the router's full
    width."""
    with part("router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            w["gate.weight"].astype(jnp.float32),
                            precision=_HI)
        idx, wts = sigmoid_topk_routing(
            logits, w["gate.e_score_correction_bias"],
            cfg.num_experts_per_tok, scaling=cfg.routed_scaling_factor,
            normalize_topk=True)
        return moe_grouped.held_rows(idx, cfg.expert_offset,
                                     cfg.num_local_experts), wts


def moe_prefill(w: Dict, cfg: MiniMaxM3Config, x):
    """x (T, C): the picks that fall on held experts, rows grouped by
    expert, plus the shared expert -> (y (T, C), the picks that fell
    here)."""
    idx, wts = route(w, cfg, x)
    with part("experts"):
        here = idx < cfg.num_local_experts
        y = moe_grouped.moe_grouped_ffn_prefill(
            x, idx, jnp.where(here, wts, 0.0), w["experts.w_gate"],
            w["experts.w_up"], w["experts.w_down"], act=cfg.act)
    with part("ffn"):
        y = y + _mlp(_sub(w, "shared_experts."), cfg, x)
    with part("router"):
        return y, here.sum(dtype=jnp.int32)


def msa_chunk(w: Dict, cfg: MiniMaxM3Config, x, kv, pk, valid, c0, cos, sin):
    """One layer's attention over a chunk: x (n, C, C_h) normed at
    positions ``c0 ..``; kv (n, S, 2 G d) and pk (n, S / block, G d) the
    rows and pooled keys so far; valid (n, C) the chunk's true tokens ->
    (y, kv', pk', the (n, C, G, NB) blocks the queries read)."""
    sp = cfg.msa
    n, C, _ = x.shape
    G, d = cfg.num_key_value_heads, cfg.head_dim
    with part("attn_in"):
        q, k, v = attn_inputs(w, cfg, x, cos, sin)
    with part("attn"):
        kv = lax.dynamic_update_slice_in_dim(
            kv, jnp.concatenate([k, v], -1).astype(kv.dtype), c0, axis=1)
    with part("select"):
        u = index_queries(w, cfg, x, cos, sin)
        pk = lax.dynamic_update_slice_in_dim(
            pk, msa.pool_chunk(k.astype(pk.dtype), valid, sp),
            c0 // sp.block_size, axis=1)
        blocks = msa.prefill_select(u, pk.reshape(n, -1, G, d), c0, sp)
    with part("attn"):
        o = spg.sparse_prefill_attention(q, kv, blocks, c0 + C, groups=G,
                                         block_size=sp.block_size)
    with part("attn_out"):
        y = _lin(w, "o_proj", o.reshape(n, C, -1).astype(x.dtype))
    return y, kv, pk, blocks


def init_cache(cfg: MiniMaxM3Config, n: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """The prefill's carry: ``kv`` (layers, n, len, 2 G d) in ``dtype``
    and ``pk`` (layers, n, len / block, G d) float32."""
    bs = cfg.msa_block_size
    if max_len % bs:
        raise ValueError(f"cache length {max_len} is not a multiple of the "
                         f"MSA block {bs}")
    L, gd = cfg.num_hidden_layers, cfg.num_key_value_heads * cfg.head_dim
    return {"kv": jnp.zeros((L, n, max_len, 2 * gd), dtype),
            "pk": jnp.zeros((L, n, max_len // bs, gd), jnp.float32)}


def chunks_a_call(cfg: MiniMaxM3Config, C: int, chunks: int) -> int:
    """How many chunks of C positions of one row of a wave send their
    routed rows through ONE call of the prefill expert kernel: the most,
    up to ``chunks``, whose mean rows a held expert (``C k /
    router_experts`` a chunk) plus three standard deviations of a random
    router's spread (the mean's square root) stay inside one row tile
    of the kernel where experts arrive in slices. A call streams every
    held expert once a row tile, so up to the tile the chunks share one
    stream. One where a chunk's rows already fill the tile."""
    tile = moe_grouped._row_tile(0, 0, sliced=True)
    per = C * cfg.num_experts_per_tok / cfg.router_experts

    def fits(g):
        return g * per + 3 * math.sqrt(g * per) <= tile

    g = 1
    while g < chunks and fits(g + 1):
        g += 1
    return g


def prefill_groups(cfg: MiniMaxM3Config, s: int):
    """A prefill of s positions -> (its chunk C, the chunks of each group
    in order): groups of :func:`chunks_a_call` chunks, the last one
    smaller where they do not divide the chunks. One call of the expert
    kernel a group an expert layer."""
    C = max(la.chunk_size(s, cfg.prefill_chunk), 1)
    n = s // C
    g = chunks_a_call(cfg, C, n)
    return C, [g] * (n // g) + ([n % g] if n % g else [])


def hidden_forward(w: Dict, cfg: MiniMaxM3Config, ids, cache: Dict,
                   true_len=None, positions=None, return_blocks=False):
    """ids (n, s) from position 0, in groups of chunks
    (:func:`prefill_groups`) -> (the residual before the final norm: (n,
    s, C), or (n, C) at ``positions`` (n,); the cache after the s
    positions, its pooled keys over each row's ``true_len`` (n,) tokens;
    the picks that fell on held experts over every expert layer and
    chunk; with ``return_blocks`` also the blocks every query read,
    (layers, n, s, G, NB))."""
    n, s = ids.shape
    C, sizes = prefill_groups(cfg, s)
    L, eps = cfg.num_hidden_layers, cfg.rms_norm_eps
    true_len = (jnp.full((n,), s, jnp.int32) if true_len is None
                else true_len.astype(jnp.int32))
    emb = w["model.embed_tokens.weight"]
    # one trace of a chunk's attention for every layer and group: tracing
    # is part of the set-up of every prefill program
    attend_chunk = jax.jit(lambda aw, *a: msa_chunk(aw, cfg, *a))

    def group(carry, p0, m: int):
        """The m chunks from position p0: a layer's attention a chunk at
        a time, in order, then its FFN over the m chunks' rows at once.
        Outputs chunk-major: x (m, n, C, h), blocks (m, L, n, C, G,
        NB)."""
        cache, picked, rows = carry
        c0 = p0 + C * jnp.arange(m)
        pos = c0[:, None] + jnp.arange(C)                       # (m, C)
        with part("embed"):
            tok = lax.dynamic_slice_in_dim(ids, p0, m * C, axis=1)
            x = jnp.take(emb, tok.reshape(n, m, C).swapaxes(0, 1), axis=0)
        with part("attn_in"):
            cos, sin = _rope(cfg, pos)
        valid = pos[:, None] < true_len[None, :, None]          # (m, n, C)
        blocks = []
        for l in range(L):
            lw = _sub(w, f"model.layers.{l}.")
            aw = _sub(lw, "self_attn.")
            with part("norm"):
                xn = _norm(x, lw["input_layernorm.weight"], eps)

            def attend(cache, xs):
                xc, vc, cc, co, si = xs
                y, kv, pk, blk = attend_chunk(aw, xc, cache["kv"][l],
                                              cache["pk"][l], vc, cc, co, si)
                with part("attn"):
                    cache = {"kv": cache["kv"].at[l].set(kv),
                             "pk": cache["pk"].at[l].set(pk)}
                return cache, (y, blk if return_blocks else None)

            cache, (y, blk) = lax.scan(attend, cache,
                                       (xn, valid, c0, cos, sin))
            blocks.append(blk)
            with part("attn_out"):
                x = x + y
            with part("norm"):
                xn = _norm(x, lw["post_attention_layernorm.weight"], eps)
            if cfg.is_moe(l):
                y, r = moe_prefill(_sub(lw, "mlp."), cfg,
                                   xn.reshape(m * n * C, -1))
                y = y.reshape(x.shape)
                with part("router"):
                    rows = rows + r
            else:
                with part("ffn"):
                    y = _mlp(_sub(lw, "mlp."), cfg, xn)
            with part("ffn"):
                x = x + y
        out = {}
        if positions is None:
            out["x"] = x
        else:
            with part("head"):
                at = jnp.clip(positions - p0, 0, m * C - 1)
                here = (positions >= p0) & (positions < p0 + m * C)
                row = x[at // C, jnp.arange(n), at % C]
                picked = jnp.where(here[:, None], row, picked)
        if return_blocks:
            out["blocks"] = jnp.stack(blocks, 1)
        return (cache, picked, rows), out

    g = sizes[0]
    full = sizes.count(g)
    carry = (cache, jnp.zeros((n, emb.shape[1]), emb.dtype),
             jnp.zeros((), jnp.int32))
    carry, out = lax.scan(lambda c, i: group(c, i * g * C, g), carry,
                          jnp.arange(full))
    last = None
    if len(sizes) > full:       # the smaller last group, at its own size
        carry, last = group(carry, full * g * C, sizes[-1])
    cache, picked, rows = carry

    def chunks(name):
        """Every chunk's ``name`` output in order, the chunks first."""
        o = out[name].reshape(-1, *out[name].shape[2:])
        return o if last is None else jnp.concatenate([o, last[name]])

    h = (picked if positions is not None else
         jnp.moveaxis(chunks("x"), 0, 1).reshape(n, s, -1))
    if return_blocks:
        b = jnp.moveaxis(chunks("blocks"), 0, 2)  # (L, n, chunks, C, ..)
        return h, cache, rows, b.reshape(L, n, s, *b.shape[4:])
    return h, cache, rows


def head_forward(w: Dict, cfg: MiniMaxM3Config, h):
    with part("head"):
        return jnp.matmul(_norm(h, w["model.norm.weight"], cfg.rms_norm_eps),
                          w["lm_head.weight"])


def decode_step(w: Dict, cfg: MiniMaxM3Config, x, pool, tables, positions):
    """One token a row through every layer: x (b, C) embeddings; pool
    ``(kv (L, NB, BT, 2 G d), pk (L, NB, BT / block, G d))``; tables (b,
    MB); positions (b,). A row whose table starts at the scratch block
    is idle: it appends nothing and routes to no expert. -> (the
    residual (b, C), pool, int32 (9,): :data:`STEP_COUNTERS`)."""
    sp, eps = cfg.msa, cfg.rms_norm_eps
    held, k = cfg.num_local_experts, cfg.num_experts_per_tok
    kv_pool, pk_pool = pool
    active = tables[:, 0] != 0
    with part("attn_in"):
        cos, sin = _rope(cfg, positions)                    # (b, r)
    sparse = jnp.zeros(3, jnp.int32)
    moe = jnp.zeros(3, jnp.int32)
    n_moe = 0
    for l in range(cfg.num_hidden_layers):
        lw = _sub(w, f"model.layers.{l}.")
        aw = _sub(lw, "self_attn.")
        with part("norm"):
            xn = _norm(x, lw["input_layernorm.weight"], eps)
        with part("attn_in"):
            q, kk, v = attn_inputs(aw, cfg, xn, cos, sin)
        with part("attn"):
            kv_pool, pk_pool = msa.append_kv(
                kv_pool, pk_pool, tables, positions, kk, v, active, layer=l,
                sp=sp)
        with part("select"):
            u = index_queries(aw, cfg, xn, cos, sin)
            blocks, c = msa.decode_select(u, pk_pool, tables, positions,
                                          active, layer=l, sp=sp)
            sparse = sparse + c
        with part("attn"):
            o = spg.sparse_paged_decode(q, kv_pool, tables, positions,
                                        blocks, layer=l, sp=sp)
        with part("attn_out"):
            x = x + _lin(aw, "o_proj", o.reshape(o.shape[0], -1)
                         .astype(x.dtype))
        with part("norm"):
            xn = _norm(x, lw["post_attention_layernorm.weight"], eps)
        mw = _sub(lw, "mlp.")
        if cfg.is_moe(l):
            idx, wts = route(mw, cfg, xn)
            with part("experts"):
                y = moe_grouped.moe_grouped_ffn_decode(
                    xn, moe_grouped.dense_weights(idx, wts, active, held),
                    mw["experts.w_gate"], mw["experts.w_up"],
                    mw["experts.w_down"], act=cfg.act)
            with part("ffn"):
                y = y + _mlp(_sub(mw, "shared_experts."), cfg, xn)
            with part("router"):
                moe = moe + moe_grouped.routing_counts(idx, active, held)
            n_moe += 1
        else:
            with part("ffn"):
                y = _mlp(mw, cfg, xn)
        with part("ffn"):
            x = x + y
    with part("router"):
        picks = active.sum(dtype=jnp.int32) * (k * n_moe)
    return x, (kv_pool, pk_pool), jnp.concatenate([
        sparse, jnp.zeros((1,), jnp.int32), jnp.full((1,), n_moe, jnp.int32),
        moe, picks[None]])


# -------------------------------------------------------------- the layers
class MiniMaxM3Attention(nn.Layer):
    """q, k, v and output projections, the indexer's query projection,
    and the per-head norms of q, k and the indexer's queries."""

    def __init__(self, cfg: MiniMaxM3Config):
        super().__init__()
        C, std, d = cfg.hidden_size, cfg.initializer_range, cfg.head_dim
        H, G = cfg.num_attention_heads, cfg.num_key_value_heads
        self.q_proj = _proj(C, H * d, std)
        self.k_proj = _proj(C, G * d, std)
        self.v_proj = _proj(C, G * d, std)
        self.o_proj = _proj(H * d, C, std)
        self.index_proj = _proj(C, G * cfg.msa_index_heads * d, std)
        self.q_norm = nn.RMSNorm(d, epsilon=cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(d, epsilon=cfg.rms_norm_eps)
        self.index_norm = nn.RMSNorm(d, epsilon=cfg.rms_norm_eps)


class MiniMaxM3Gate(nn.Layer):
    """The router over ALL ``router_experts``, whatever is held."""

    def __init__(self, cfg: MiniMaxM3Config):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.router_experts),
            default_initializer=init.Normal(0.0, cfg.initializer_range))
        self.e_score_correction_bias = self.create_parameter(
            (cfg.router_experts,), default_initializer=init.Constant(0.0))


class MiniMaxM3MoE(nn.Layer):
    def __init__(self, cfg: MiniMaxM3Config):
        super().__init__()
        std = cfg.initializer_range
        self.gate = MiniMaxM3Gate(cfg)
        self.experts = GroupedSwiGLUExperts(
            cfg.num_local_experts, cfg.hidden_size, cfg.intermediate_size,
            initializer_range=std)
        self.shared_experts = Xing4MLP(
            cfg.hidden_size,
            cfg.shared_intermediate_size * cfg.n_shared_experts, std)


class MiniMaxM3DecoderLayer(nn.Layer):
    def __init__(self, cfg: MiniMaxM3Config, moe: bool):
        super().__init__()
        C = cfg.hidden_size
        self.input_layernorm = nn.RMSNorm(C, epsilon=cfg.rms_norm_eps)
        self.self_attn = MiniMaxM3Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(C,
                                                   epsilon=cfg.rms_norm_eps)
        self.mlp = (MiniMaxM3MoE(cfg) if moe else
                    Xing4MLP(C, cfg.dense_intermediate_size,
                                 cfg.initializer_range))


class MiniMaxM3Model(nn.Layer):
    def __init__(self, cfg: MiniMaxM3Config):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=init.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([
            MiniMaxM3DecoderLayer(cfg, cfg.is_moe(i))
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)


class MiniMaxM3ForCausalLM(CausalLMBase):
    def __init__(self, cfg: MiniMaxM3Config):
        super().__init__()
        if cfg.tie_word_embeddings:
            raise ValueError("MiniMaxM3ForCausalLM has an untied output head")
        self.cfg = cfg
        self.model = MiniMaxM3Model(cfg)
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
                positions: Optional[jax.Array] = None,
                moe_rows: bool = False):
        """Logits (b, s, vocab) over the rows of the vocabulary held
        here; with ``positions`` (b,) only each row's logits at that
        position, (b, vocab), and the rest of the row is pad: its keys
        enter no pooled key. With ``cache`` (from ``init_cache``, at
        least s long) also the filled cache; with ``moe_rows`` also,
        last, the picks that fell on held experts (int32 scalar). A
        forward starts at position 0."""
        del attn_mask       # causal; serving pads on the right
        if not (isinstance(start_pos, int) and start_pos == 0):
            raise ValueError("MiniMaxM3ForCausalLM prefills from position 0 "
                             "only (the pooled keys of a shared prefix are "
                             "not kept)")
        w, cfg = self._weights(), self.cfg
        b, s = input_ids.shape
        own = cache if cache is not None else init_cache(
            cfg, b, s, w["model.embed_tokens.weight"].dtype)
        h, own, rows = hidden_forward(
            w, cfg, input_ids, own, positions=positions,
            true_len=None if positions is None else positions + 1)
        out = (head_forward(w, cfg, h),)
        out += () if cache is None else (own,)
        out += (rows,) if moe_rows else ()
        return out[0] if len(out) == 1 else out

    def fused_decode_plan(self, state, probe=False):
        """What ``serving.ServingEngine`` asks of a model (docs/SERVING.md
        §Architectures the engine takes): ``arch`` ``"msa_moe"``, pool
        rows ``[k | v]`` for every layer, the pooled keys as a second
        paged leaf (``pool_aux``: one row a block), no state a slot,
        ``to_lanes`` from the prefill's cache, the MSA and expert kernels
        a wave sends (``prefill_calls``: a call a chunk a layer;
        ``prefill_moe`` with ``rows`` ``"counted"`` and ``calls_a_layer``
        the wave's groups of chunks, :func:`prefill_groups`: one expert
        kernel call a group an expert layer), and a step that counts
        blocks and experts."""
        if "model.layers.0.self_attn.index_proj.weight" not in state:
            return None     # a quantized or otherwise foreign state
        cfg = self.cfg
        sp = cfg.msa
        gd = cfg.num_key_value_heads * cfg.head_dim
        L = cfg.num_hidden_layers

        def groups(s_pad: int) -> int:
            return len(prefill_groups(cfg, s_pad)[1])

        def prefill_calls(R: int, s_pad: int) -> Dict:
            del R
            C = max(la.chunk_size(s_pad, cfg.prefill_chunk), 1)
            n = s_pad // C
            # the chunks that end past dense_len: the others score nothing
            return {"msa_prefill_calls": L * n,
                    "msa_select_calls": L * (n - min(sp.dense_len // C, n))}

        meta = {
            "arch": "msa_moe", "cache_lanes": 2 * gd, "pool_layers": L,
            "pool_aux": {"stride": sp.block_size, "lanes": gd,
                         "dtype": jnp.float32},
            "to_lanes": lambda cache: (cache["kv"], cache["pk"]),
            "to_state": lambda cache: {},
            "prefill_calls": prefill_calls,
            "prefill_moe": {
                "layers": sum(cfg.moe_layer_freq),
                "calls_a_layer": groups,
                "k": cfg.num_experts_per_tok,
                "path": moe_grouped.prefill_path(cfg.hidden_size,
                                                 cfg.intermediate_size),
                "rows": "counted"},
            "step_counters": STEP_COUNTERS}
        if probe:
            return meta

        def embed(tok, pos):
            del pos
            with part("embed"):
                return jnp.take(state["model.embed_tokens.weight"], tok,
                                axis=0)

        def step(x, pool, tables, positions, slot_state):
            x, pool, counts = decode_step(state, cfg, x, pool, tables,
                                          positions)
            return x, pool, slot_state, counts

        def head(x):
            return head_forward(state, cfg, x)

        return dict(meta, embed=embed, step=step, head=head)
