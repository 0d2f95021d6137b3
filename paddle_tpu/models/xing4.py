"""Xing4.0-29B-A4B: latent attention, routed experts, a four-stream
residual and one multi-token-prediction layer.

The published ``config.json`` (``model_type`` ``xing4_0``) names three
mechanisms by their papers' keys:

* **MLA** (DeepSeek-V2): queries through a ``q_lora_rank`` bottleneck,
  keys and values through one ``kv_lora_rank`` latent a token plus one
  rotary key shared by all heads; YaRN rotary frequencies. The cache is
  the latent: ``kv_lora_rank + qk_rope_head_dim`` values a token a
  layer. Prefill expands it into per-head keys and values
  (:func:`mla_expanded`); a decode step stays in the latent space
  (:func:`mla_absorb_query`, ``ops.mla_decode``, :func:`mla_absorb_out`).
* **Routed experts** (DeepSeek-V3 ``noaux_tc``): sigmoid scores, a
  correction bias that steers the choice only, top-k weights normalised
  and scaled, one shared expert beside them; the first
  ``first_k_dense_replace`` layers are dense SwiGLU.
* **mHC** (arXiv:2512.24880): ``hc_mult`` residual streams mixed around
  every sub-layer (``nn.layers.hyper_connection``).
* **MTP** (DeepSeek-V3): ``forward(ids, mtp=True)`` also returns the
  logits of one further block that predicts token t + 2 from the main
  model's state at t and the embedding of token t + 1.

All the mathematics is in module-level functions over ``{name: array}``
weights; the ``Layer`` classes only own the parameters, so that the
layered forward (``forward``, what ``generate`` and the engine's prefill
run) and the paged decode step (``fused_decode_plan(...)["step"]``, what
the engine's step program runs) read the SAME leaves of one state: the
weights are held once.
"""

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.models.llama import CausalLMBase
from paddle_tpu.nn import initializer as init
from paddle_tpu.nn.layers import hyper_connection as hc
from paddle_tpu.nn.layers.moe import (GroupedSwiGLUExperts,
                                      sigmoid_topk_routing)
from paddle_tpu.ops import mla_decode, mla_prefill, moe_grouped
from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.ops.rms_norm import rms_norm
from paddle_tpu.profiler.parts import part

_HI = jax.lax.Precision.HIGHEST
# what ``decode_step`` counts, in the order it returns them
STEP_COUNTERS = ("moe_layer_steps", "moe_experts_touched", "moe_rows_max",
                 "moe_rows")


def _yarn_default():
    return {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


class MLAConfig:
    """What the MLA functions below read of a configuration besides its
    fields (``num_heads``, ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_
    head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rms_norm_eps``,
    ``rope_theta``, ``rope_scaling``): the base of every configuration
    whose attention is MLA (``models.deepseek_v2`` too)."""

    @property
    def latent_dim(self) -> int:
        """What one token keeps in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        rs = self.rope_scaling
        m = rope_ops.yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * m * m)


@dataclasses.dataclass
class Xing4Config(MLAConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216           # the leading dense layers
    moe_intermediate_size: int = 1024       # one expert, and the shared one
    num_layers: int = 40
    first_k_dense_replace: int = 2
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Dict = dataclasses.field(default_factory=_yarn_default)
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    @classmethod
    def tiny(cls, vocab_size=256, **over):
        """Every mechanism at toy widths: 1 dense + 2 expert layers, 8
        experts top-2, 4 streams, the MTP layer."""
        kw = dict(vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_layers=3,
                  first_k_dense_replace=1, num_heads=4, q_lora_rank=48,
                  kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
                  max_position_embeddings=512,
                  rope_scaling=dict(_yarn_default(), factor=4,
                                    original_max_position_embeddings=64))
        kw.update(over)
        return cls(**kw)

    def hc_options(self) -> Dict:
        return dict(sinkhorn_iters=self.hc_sinkhorn_iters, eps=self.hc_eps,
                    norm_eps=self.rms_norm_eps,
                    clamp=(self.mhc_h_res_clamp_min,
                           self.mhc_h_res_clamp_max))


# ----------------------------------------------------------- the functions
def _sub(w: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def rope_tables(cfg: MLAConfig, positions):
    """(cos, sin), each ``positions.shape + (qk_rope_head_dim,)``."""
    rs = cfg.rope_scaling
    return rope_ops.yarn_cos_sin(
        None, cfg.qk_rope_head_dim, base=cfg.rope_theta, factor=rs["factor"],
        original_max_position_embeddings=rs[
            "original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs.get("mscale", 1), mscale_all_dim=rs.get("mscale_all_dim", 0),
        position_ids=positions)


def _swiglu(w: Dict, x):
    g = jnp.matmul(x, w["gate_proj.weight"])
    u = jnp.matmul(x, w["up_proj.weight"])
    return jnp.matmul(jax.nn.silu(g) * u, w["down_proj.weight"])


def mla_project(w: Dict, cfg: MLAConfig, x, cos, sin):
    """x (b, s, C) -> q_n (b, s, H, d_n), q_r (b, s, H, d_r) after rope,
    and the token's cache row ``[RMSNorm(c_kv) | rope(k_r)]``
    (b, s, d_c + d_r). cos, sin: (s, d_r) or (b, s, d_r)."""
    b, s, _ = x.shape
    H, dn, dr, dc = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    eps = cfg.rms_norm_eps
    c_q = rms_norm(jnp.matmul(x, w["q_a_proj.weight"]),
                   w["q_a_layernorm.weight"], eps)
    q = jnp.matmul(c_q, w["q_b_proj.weight"]).reshape(b, s, H, dn + dr)
    kv = jnp.matmul(x, w["kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(kv[..., :dc], w["kv_a_layernorm.weight"], eps)
    q_r = rope_ops.apply_rotary_pos_emb(q[..., dn:], cos, sin)
    k_r = rope_ops.apply_rotary_pos_emb(kv[..., None, dc:], cos, sin)[:, :, 0]
    return q[..., :dn], q_r, jnp.concatenate([c_kv, k_r], axis=-1)


def _kvb(w: Dict, cfg: MLAConfig):
    """``W_kvb`` as (d_c, H, d_n + d_v): a head's key and value halves."""
    return w["kv_b_proj.weight"].reshape(
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def _attn_plan(cfg: MLAConfig, s: int, S: int, start_pos, itemsize: int):
    """``ops.mla_prefill.kernel_plan`` at this configuration's head
    sizes: not None where the expanded attention of ``s`` queries over
    ``S`` cached rows takes the flash kernel here."""
    return mla_prefill.kernel_plan(
        s, S, start_pos, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, itemsize)


def mla_expanded(w: Dict, cfg: MLAConfig, q_n, q_r, latent, start_pos):
    """Causal attention in the expanded form: the cached rows ``latent``
    (b, S, d_c + d_r) become per-head keys and values through ``W_kvb``.
    The queries sit at positions ``start_pos + arange(s)``. Through
    ``ops.mla_prefill``: its flash kernel where :func:`_attn_plan` takes
    the shapes (a TPU, a Python ``start_pos`` with ``S == start_pos +
    s``, both multiples of 128), the expansion then written head-major
    for it; else its ``jnp`` reference. -> (b, s, H * d_v)."""
    s, dn = q_n.shape[1], q_n.shape[-1]
    dc = cfg.kv_lora_rank
    c_kv, k_r, kvb = latent[..., :dc], latent[..., dc:], _kvb(w, cfg)
    if _attn_plan(cfg, s, latent.shape[1], start_pos,
                  q_n.dtype.itemsize) is not None:
        with part("attn_in"):
            k_n = jnp.einsum("bsc,chd->bhsd", c_kv, kvb[..., :dn])
            v = jnp.einsum("bsc,chd->bhsd", c_kv, kvb[..., dn:])
        return mla_prefill.mla_flash_prefill(
            q_n, q_r, k_n, v, k_r, scale=cfg.softmax_scale,
            start_pos=start_pos)
    with part("attn_in"):
        kv = jnp.einsum("bsc,chd->bshd", c_kv, kvb)
    with part("attn"):
        return mla_prefill.reference(q_n, q_r, kv[..., :dn], kv[..., dn:],
                                     k_r, cfg.softmax_scale, start_pos)


def mla_absorb_query(w: Dict, cfg: MLAConfig, q_n, q_r, lanes: int):
    """(b, H, d_n), (b, H, d_r) -> the query in the cache row's own
    layout ``[q_n W_kvb,k^T | q_r | 0]`` (b, H, lanes)."""
    q_c = jnp.einsum("bhd,chd->bhc", q_n,
                     _kvb(w, cfg)[..., :cfg.qk_nope_head_dim])
    return mla_decode.pad_lanes(jnp.concatenate([q_c, q_r], -1), lanes)


def mla_absorb_out(w: Dict, cfg: MLAConfig, o_c):
    """The latent-space attention output (b, H, d_c) through the value
    half of ``W_kvb`` -> (b, H * d_v)."""
    kvb = _kvb(w, cfg)
    o = jnp.einsum("bhc,chd->bhd", o_c.astype(kvb.dtype),
                   kvb[..., cfg.qk_nope_head_dim:])
    return o.reshape(o.shape[0], -1)


def mla_layered(w: Dict, cfg: MLAConfig, xn, cos, sin, cache, start_pos):
    """The attention sub-layer of a layered forward on the normed xn
    (b, s, C): project, write the rows into ``cache["ckv"]`` at
    ``start_pos`` (or attend over the block's own rows when ``cache`` is
    None), expanded attention, ``W_o``. -> (y (b, s, C), cache')."""
    with part("attn_in"):
        q_n, q_r, lat = mla_project(w, cfg, xn, cos, sin)
    if cache is not None:
        with part("attn"):
            full = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], lat.astype(cache["ckv"].dtype), start_pos,
                axis=1)
        cache = {"ckv": full}
    else:
        full = lat
    att = mla_expanded(w, cfg, q_n, q_r, full.astype(lat.dtype), start_pos)
    with part("attn_out"):
        return jnp.matmul(att, w["o_proj.weight"]), cache


def mla_paged(w: Dict, cfg: MLAConfig, xn, cos, sin, pool, tables, positions,
              layer: int):
    """The attention sub-layer of one decode step on the normed xn
    (b, C) over the paged latent pool, absorbed. -> (y (b, C), pool)."""
    lanes = pool.shape[-1]
    with part("attn_in"):
        q_n, q_r, lat = mla_project(w, cfg, xn[:, None], cos[:, None],
                                    sin[:, None])
        q = mla_absorb_query(w, cfg, q_n[:, 0], q_r[:, 0], lanes)
        new = mla_decode.pad_lanes(lat[:, 0], lanes)
    with part("attn"):
        o_c, pool = mla_decode.mla_paged_decode(
            q, new, pool, tables, positions, layer=layer,
            d_c=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    with part("attn_out"):
        return jnp.matmul(mla_absorb_out(w, cfg, o_c).astype(xn.dtype),
                          w["o_proj.weight"]), pool


def latent_plan(cfg: MLAConfig, itemsize: int) -> Dict:
    """The part of a ``fused_decode_plan`` that the latent cache
    decides: ``arch`` ``"mla_moe"``, the pool's ``cache_lanes``,
    ``to_lanes`` / ``from_lanes`` between the prefill cache
    (``[{"ckv": (n, len, d_c + d_r)}]`` a layer) and pool rows, and
    ``prefill_attn_calls(R, s_pad)``: the layers of a wave prefill of
    ``s_pad`` positions behind ``R`` cached ones whose attention takes
    the flash kernel (the predicate :func:`mla_expanded` dispatches on,
    at activations of ``itemsize`` bytes), and ``decode_walk``: the
    decode kernel's walk of a step, counted on the host
    (``ops.mla_decode.mla_walk_counts``)."""
    lanes = mla_decode.pool_lanes(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    ld = cfg.latent_dim

    def to_lanes(cache):
        """[{"ckv": (n, len, ld)}] -> (L, n, len, lanes)."""
        return jnp.stack([mla_decode.pad_lanes(c["ckv"], lanes)
                          for c in cache])

    def from_lanes(cache, rows):
        """Write pool rows (L, n, R, lanes) at the cache's start."""
        R = rows.shape[2]
        return [{"ckv": c["ckv"].at[:, :R].set(
            rows[l, :, :, :ld].astype(c["ckv"].dtype))}
            for l, c in enumerate(cache)]

    def prefill_attn_calls(R: int, s_pad: int) -> int:
        takes = _attn_plan(cfg, s_pad, R + s_pad, R, itemsize) is not None
        return cfg.num_layers if takes else 0

    return {"arch": "mla_moe", "cache_lanes": lanes, "to_lanes": to_lanes,
            "from_lanes": from_lanes,
            "prefill_attn_calls": prefill_attn_calls,
            "decode_walk": mla_decode.mla_walk_counts}


def _itemsize(state: Dict) -> int:
    """Bytes of an activation of a model with this state."""
    return state["model.embed_tokens.weight"].dtype.itemsize


def route(w: Dict, cfg: Xing4Config, x):
    """x (T, C) -> (idx (T, k), weights (T, k) float32); the scores in
    float32 at full matmul precision (a rounded score picks another
    expert)."""
    with part("router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            w["gate.weight"].astype(jnp.float32),
                            precision=_HI)
        return sigmoid_topk_routing(
            logits, w["gate.e_score_correction_bias"],
            cfg.num_experts_per_tok, scaling=cfg.routed_scaling_factor,
            normalize_topk=cfg.norm_topk_prob)


def moe_ragged(w: Dict, cfg: Xing4Config, x):
    """x (T, C): the routed experts over rows sorted by expert (no token
    dropped) plus the shared expert. What prefill and ``generate`` run:
    the grouped kernel or ``ragged_dot``, as
    ``ops.moe_grouped.prefill_path`` says for these widths here."""
    idx, wts = route(w, cfg, x)
    with part("experts"):
        y = moe_grouped.moe_grouped_ffn_prefill(
            x, idx, wts, w["experts.w_gate"], w["experts.w_up"],
            w["experts.w_down"])
    with part("ffn"):
        return y + _swiglu(_sub(w, "shared_experts."), x)


def _is_moe(cfg: Xing4Config, layer: int) -> bool:
    return layer >= cfg.first_k_dense_replace


def _hc(w: Dict, cfg: Xing4Config, X):
    with part("mix"):
        h_pre, h_post, h_res = hc.hc_mixers(
            X, w["phi"], w["alpha_pre"], w["alpha_post"], w["alpha_res"],
            w["pre_bias"], w["post_bias"], w["res_bias"], **cfg.hc_options())
        return hc.hc_read(X, h_pre), h_post, h_res


def _hc_write(X, y, h_post, h_res):
    with part("mix"):
        return hc.hc_write(X, y, h_post, h_res)


def block_forward(w: Dict, cfg: Xing4Config, moe: bool, X, cos, sin, cache,
                  start_pos):
    """One decoder block on X (b, s, n, C), layered: attention in the
    expanded form over ``cache["ckv"]`` (or over the block's own rows
    when ``cache`` is None). -> (X', cache')."""
    b, s = X.shape[:2]
    eps = cfg.rms_norm_eps
    h, h_post, h_res = _hc(_sub(w, "attn_hc."), cfg, X)
    with part("norm"):
        xn = rms_norm(h, w["input_layernorm.weight"], eps)
    y, cache = mla_layered(_sub(w, "self_attn."), cfg, xn, cos, sin, cache,
                           start_pos)
    X = _hc_write(X, y, h_post, h_res)
    h, h_post, h_res = _hc(_sub(w, "ffn_hc."), cfg, X)
    with part("norm"):
        xn = rms_norm(h, w["post_attention_layernorm.weight"], eps)
    if moe:
        y = moe_ragged(_sub(w, "mlp."), cfg,
                       xn.reshape(b * s, -1)).reshape(b, s, -1)
    else:
        with part("ffn"):
            y = _swiglu(_sub(w, "mlp."), xn)
    return _hc_write(X, y, h_post, h_res), cache


def _streams(cfg: Xing4Config, x):
    """Read-in: the embedding copied to every stream."""
    return jnp.broadcast_to(x[..., None, :],
                            x.shape[:-1] + (cfg.hc_mult, x.shape[-1]))


def hidden_forward(w: Dict, cfg: Xing4Config, ids, cache=None, start_pos=0):
    """ids (b, s) -> (the streams' sum before the final norm (b, s, C),
    cache')."""
    s = ids.shape[1]
    with part("attn_in"):
        cos, sin = rope_tables(cfg, start_pos + jnp.arange(s))
    with part("embed"):
        X = _streams(cfg, jnp.take(w["model.embed_tokens.weight"], ids,
                                   axis=0))
    new_cache = []
    for i in range(cfg.num_layers):
        X, c = block_forward(_sub(w, f"model.layers.{i}."), cfg,
                             _is_moe(cfg, i), X, cos, sin,
                             None if cache is None else cache[i], start_pos)
        new_cache.append(c)
    with part("mix"):
        return X.sum(axis=-2), (None if cache is None else new_cache)


def head_forward(w: Dict, cfg: Xing4Config, h, norm="model.norm.weight"):
    with part("head"):
        return jnp.matmul(rms_norm(h, w[norm], cfg.rms_norm_eps),
                          w["lm_head.weight"])


def mtp_forward(w: Dict, cfg: Xing4Config, h, ids):
    """The MTP layer: h (b, s, C) the main model's state, ids (b, s).
    -> logits (b, s - 1, vocab); row t predicts token t + 2."""
    eps = cfg.rms_norm_eps
    s = ids.shape[1] - 1
    with part("embed"):
        emb = jnp.take(w["model.embed_tokens.weight"], ids[:, 1:], axis=0)
        both = jnp.concatenate(
            [rms_norm(h[:, :-1], w["model.mtp.hnorm.weight"], eps),
             rms_norm(emb, w["model.mtp.enorm.weight"], eps)], axis=-1)
        x = jnp.matmul(both, w["model.mtp.eh_proj.weight"])
    with part("attn_in"):
        cos, sin = rope_tables(cfg, jnp.arange(s))
    X, _ = block_forward(_sub(w, "model.mtp.block."), cfg, True,
                         _streams(cfg, x), cos, sin, None, 0)
    return head_forward(w, cfg, X.sum(axis=-2), norm="model.mtp.norm.weight")


def decode_step(w: Dict, cfg: Xing4Config, x, pool, tables, positions):
    """One token a row through every block over the PAGED latent pool:
    x (b, C) embeddings, pool (L, NB, BT, lanes), tables (b, MB),
    positions (b,). A row whose table starts at the scratch block is
    idle: it routes to no expert. -> (the streams' sum (b, C), pool,
    int32 (4,): expert layers, experts touched, the fullest expert's
    rows, active rows x k, the last three summed over the layers)."""
    eps = cfg.rms_norm_eps
    active = tables[:, 0] != 0
    with part("attn_in"):
        cos, sin = rope_tables(cfg, positions)              # (b, d_r)
    with part("embed"):
        X = _streams(cfg, x)
    counts = jnp.zeros(3, jnp.int32)
    n_moe = 0
    for i in range(cfg.num_layers):
        lw = _sub(w, f"model.layers.{i}.")
        h, h_post, h_res = _hc(_sub(lw, "attn_hc."), cfg, X)
        with part("norm"):
            xn = rms_norm(h, lw["input_layernorm.weight"], eps)
        y, pool = mla_paged(_sub(lw, "self_attn."), cfg, xn, cos, sin, pool,
                            tables, positions, i)
        X = _hc_write(X, y, h_post, h_res)
        h, h_post, h_res = _hc(_sub(lw, "ffn_hc."), cfg, X)
        with part("norm"):
            xn = rms_norm(h, lw["post_attention_layernorm.weight"], eps)
        mw = _sub(lw, "mlp.")
        if _is_moe(cfg, i):
            idx, wts = route(mw, cfg, xn)
            with part("experts"):
                dense = moe_grouped.dense_weights(
                    idx, wts, active, cfg.n_routed_experts)
                y = moe_grouped.moe_grouped_ffn_decode(
                    xn, dense, mw["experts.w_gate"], mw["experts.w_up"],
                    mw["experts.w_down"])
            with part("ffn"):
                y = y + _swiglu(_sub(mw, "shared_experts."), xn)
            with part("router"):
                counts = counts + moe_grouped.routing_counts(
                    idx, active, cfg.n_routed_experts)
            n_moe += 1
        else:
            with part("ffn"):
                y = _swiglu(mw, xn)
        X = _hc_write(X, y, h_post, h_res)
    with part("mix"):
        h = X.sum(axis=-2)
    return (h, pool,
            jnp.concatenate([jnp.full((1,), n_moe, jnp.int32), counts]))


# -------------------------------------------------------------- the layers
def _proj(n_in, n_out, std):
    """A bias-free matrix ``weight`` (in, out)."""
    return nn.Linear(n_in, n_out, weight_attr=init.Normal(0.0, std),
                     bias_attr=False)


class Xing4Attention(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        C, H, std = cfg.hidden_size, cfg.num_heads, cfg.initializer_range
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _proj(C, cfg.q_lora_rank, std)
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank,
                                        epsilon=cfg.rms_norm_eps)
        self.q_b_proj = _proj(cfg.q_lora_rank, H * qk, std)
        self.kv_a_proj_with_mqa = _proj(C, cfg.latent_dim, std)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank,
                                         epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = _proj(
            cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            std)
        self.o_proj = _proj(H * cfg.v_head_dim, C, std)


class Xing4MLP(nn.Layer):
    def __init__(self, hidden, width, std):
        super().__init__()
        self.gate_proj = _proj(hidden, width, std)
        self.up_proj = _proj(hidden, width, std)
        self.down_proj = _proj(width, hidden, std)


class Xing4Gate(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.n_routed_experts),
            default_initializer=init.Normal(0.0, cfg.initializer_range))
        self.e_score_correction_bias = self.create_parameter(
            (cfg.n_routed_experts,), default_initializer=init.Constant(0.0))


class Xing4MoE(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        std = cfg.initializer_range
        self.gate = Xing4Gate(cfg)
        self.experts = GroupedSwiGLUExperts(
            cfg.n_routed_experts, cfg.hidden_size, cfg.moe_intermediate_size,
            initializer_range=std)
        self.shared_experts = Xing4MLP(
            cfg.hidden_size,
            cfg.moe_intermediate_size * cfg.n_shared_experts, std)


class Xing4DecoderLayer(nn.Layer):
    def __init__(self, cfg: Xing4Config, moe: bool):
        super().__init__()
        C = cfg.hidden_size
        mixer = lambda: hc.HyperConnection(
            cfg.hc_mult, C, initializer_range=cfg.initializer_range,
            **cfg.hc_options())
        self.attn_hc = mixer()
        self.input_layernorm = nn.RMSNorm(C, epsilon=cfg.rms_norm_eps)
        self.self_attn = Xing4Attention(cfg)
        self.ffn_hc = mixer()
        self.post_attention_layernorm = nn.RMSNorm(C,
                                                   epsilon=cfg.rms_norm_eps)
        self.mlp = (Xing4MoE(cfg) if moe else
                    Xing4MLP(C, cfg.intermediate_size,
                             cfg.initializer_range))


class Xing4MTP(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        C = cfg.hidden_size
        self.hnorm = nn.RMSNorm(C, epsilon=cfg.rms_norm_eps)
        self.enorm = nn.RMSNorm(C, epsilon=cfg.rms_norm_eps)
        self.eh_proj = _proj(2 * C, C, cfg.initializer_range)
        self.block = Xing4DecoderLayer(cfg, moe=True)
        self.norm = nn.RMSNorm(C, epsilon=cfg.rms_norm_eps)


class Xing4Model(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=init.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([
            Xing4DecoderLayer(cfg, _is_moe(cfg, i))
            for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        if cfg.num_nextn_predict_layers:
            self.mtp = Xing4MTP(cfg)


class LatentCausalLM(CausalLMBase):
    """What the causal LMs over a latent (MLA) cache share: ``self.cfg``
    an :class:`MLAConfig` with ``num_layers``, ``self.loss_fn``."""

    def _weights(self) -> Dict:
        return {n: p.value for n, p in self.named_parameters()}

    def init_cache(self, batch_size, max_len, dtype=jnp.bfloat16):
        """The latent cache: one ``{"ckv": (b, len, d_c + d_r)}`` a layer."""
        shape = (batch_size, max_len, self.cfg.latent_dim)
        return [{"ckv": jnp.zeros(shape, dtype)}
                for _ in range(self.cfg.num_layers)]

    def loss(self, logits, labels):
        return self.loss_fn(logits, labels, reduction="mean")


class Xing4ForCausalLM(LatentCausalLM):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        if cfg.tie_word_embeddings:
            raise ValueError("Xing4ForCausalLM has an untied output head")
        if cfg.num_nextn_predict_layers not in (0, 1):
            raise ValueError("Xing4ForCausalLM has at most one MTP layer, "
                             f"got {cfg.num_nextn_predict_layers}")
        self.cfg = cfg
        self.model = Xing4Model(cfg)
        self.lm_head = _proj(cfg.hidden_size, cfg.vocab_size,
                             cfg.initializer_range)
        from paddle_tpu.parallel import mp_layers as mp
        self.loss_fn = mp.ParallelCrossEntropy()

    def forward(self, input_ids, attn_mask=None, cache=None, start_pos=0,
                positions: Optional[jax.Array] = None, mtp: bool = False):
        """Logits (b, s, vocab); with ``positions`` (b,) only each row's
        logits at that position, (b, vocab): a prefill needs one row of
        the head, not s. With ``cache`` also the updated cache. With
        ``mtp=True`` (no cache) ``(logits, mtp_logits (b, s - 1, vocab))``."""
        del attn_mask       # causal; serving pads on the right
        w, cfg = self._weights(), self.cfg
        h, cache = hidden_forward(w, cfg, input_ids, cache, start_pos)
        hp = h if positions is None else jnp.take_along_axis(
            h, positions[:, None, None], axis=1)[:, 0]
        logits = head_forward(w, cfg, hp)
        if mtp:
            if cache is not None or not cfg.num_nextn_predict_layers:
                raise ValueError("mtp=True needs a model with its MTP layer "
                                 "and no cache")
            return logits, mtp_forward(w, cfg, h, input_ids)
        return logits if cache is None else (logits, cache)

    def fused_decode_plan(self, state, probe=False):
        """What ``serving.ServingEngine`` asks of a model (docs/SERVING.md
        §Architectures the engine takes): ``arch`` ``"mla_moe"``, the
        pool's ``cache_lanes``, ``to_lanes`` / ``from_lanes`` between the
        prefill cache and pool rows, the names of the step's counters, and
        with ``probe=False`` ``embed``, ``step`` and ``head`` over the
        traced ``state``: no stacked copy of the weights is made."""
        if "model.layers.0.self_attn.kv_b_proj.weight" not in state:
            return None     # a quantized or otherwise foreign state
        cfg = self.cfg
        meta = {**latent_plan(cfg, _itemsize(state)),
                "step_counters": STEP_COUNTERS,
                # what a prefill's routed experts go through here
                "prefill_moe": {
                    "layers": cfg.num_layers - cfg.first_k_dense_replace,
                    "k": cfg.num_experts_per_tok,
                    "path": moe_grouped.prefill_path(
                        cfg.hidden_size, cfg.moe_intermediate_size)}}
        if probe:
            return meta

        def embed(tok, pos):
            del pos
            with part("embed"):
                return jnp.take(state["model.embed_tokens.weight"], tok,
                                axis=0)

        def step(x, pool, tables, positions):
            return decode_step(state, cfg, x, pool, tables, positions)

        def head(x):
            return head_forward(state, cfg, x)

        return dict(meta, embed=embed, step=step, head=head)
