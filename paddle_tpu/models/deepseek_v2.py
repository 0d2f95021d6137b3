"""DeepSeek-V2: latent attention over a plain pre-norm residual, and
routed experts behind a group-limited router, held whole or as ONE
chip's share of an expert-parallel layer.

* **MLA** is ``models.xing4``'s, function for function
  (:func:`~paddle_tpu.models.xing4.mla_layered`, ``mla_paged``,
  ``rope_tables``, ``latent_plan``): queries
  through a ``q_lora_rank`` bottleneck, one ``kv_lora_rank`` latent and
  one shared rotary key a token, YaRN frequencies. With the published
  ``mscale_all_dim`` 0.707 the softmax scale is ``192^-1/2 x 1.2608^2``.
* **Routed experts** (arXiv:2405.04434): ``softmax`` scores over all
  ``n_routed_experts``, in ``n_group`` groups of consecutive experts of
  which the ``topk_group`` best are kept, the top ``num_experts_per_tok``
  of what is left, weights ``routed_scaling_factor x score`` (not
  renormalised), and ``n_shared_experts`` shared experts as one SwiGLU
  beside them. The first ``first_k_dense_replace`` layers are dense.
* **A share.** In an expert-parallel deployment a chip holds a run of
  consecutive experts (whole router groups, so that the group limit
  bounds the chips a token visits). ``experts_held`` and
  ``expert_offset`` say which: the router keeps its full width, its
  groups and its picks; this chip computes ``sum over the picks that
  fall on held experts`` plus the shared experts, and THAT goes on to
  the next layer. Nothing stands in for the other chips: the exchange
  that would add their parts is not here (PERF.md §7). A pick that is
  not here costs no row, no flop and no weight byte. ``vocab_size`` is
  the rows of the embedding and the head that are held.

The mathematics is in module-level functions over ``{name: array}``
weights, as in ``models.xing4``: the layered forward and the paged
decode step read the same leaves of one state.
"""

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.models.xing4 import (LatentCausalLM, MLAConfig,
                                     Xing4Attention, Xing4MLP, _itemsize,
                                     _proj, _sub, _swiglu, latent_plan,
                                     mla_layered, mla_paged, rope_tables)
from paddle_tpu.nn import initializer as init
from paddle_tpu.nn.layers.moe import (GroupedSwiGLUExperts,
                                      group_limited_topk_routing)
from paddle_tpu.ops import moe_grouped
from paddle_tpu.ops.rms_norm import rms_norm
from paddle_tpu.profiler.parts import part

_HI = jax.lax.Precision.HIGHEST
# what ``decode_step`` counts, in the order it returns them: the last is
# every pick of the active rows, here or not
STEP_COUNTERS = ("moe_layer_steps", "moe_experts_touched", "moe_rows_max",
                 "moe_rows", "moe_picks")


def _yarn_default():
    return {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096}


@dataclasses.dataclass
class DeepseekV2Config(MLAConfig):
    vocab_size: int = 102400                # the rows held here
    hidden_size: int = 5120
    intermediate_size: int = 12288          # the leading dense layers
    moe_intermediate_size: int = 1536       # one expert
    num_layers: int = 60
    first_k_dense_replace: int = 1
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160             # the router's width
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    experts_held: Optional[int] = None      # None: all of them
    expert_offset: int = 0                  # the first held expert's id
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Dict = dataclasses.field(default_factory=_yarn_default)
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.n_routed_experts % self.n_group:
            raise ValueError(
                f"n_routed_experts {self.n_routed_experts} is not a "
                f"multiple of n_group {self.n_group}")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"held experts {self.expert_offset}..+{self.experts_held} "
                f"lie outside the router's {self.n_routed_experts}")

    @classmethod
    def tiny(cls, vocab_size=256, **over):
        """Every mechanism at toy widths: 1 dense + 2 expert layers, 16
        experts in 4 groups of which 2 are kept, top-3, two shared."""
        kw = dict(vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_layers=3,
                  first_k_dense_replace=1, num_heads=4, q_lora_rank=48,
                  kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, n_routed_experts=16, n_group=4, topk_group=2,
                  num_experts_per_tok=3, max_position_embeddings=512,
                  rope_scaling=dict(_yarn_default(), factor=4,
                                    original_max_position_embeddings=64))
        kw.update(over)
        return cls(**kw)


# ----------------------------------------------------------- the functions
def route(w: Dict, cfg: DeepseekV2Config, x):
    """x (T, C) -> (rows of the held experts (T, k), ``experts_held``
    where the pick lies on another chip; weights (T, k) float32). The
    scores in float32 at full matmul precision (a rounded score picks
    another expert), over the router's full width."""
    with part("router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            w["gate.weight"].astype(jnp.float32),
                            precision=_HI)
        idx, wts = group_limited_topk_routing(
            logits, cfg.num_experts_per_tok, n_group=cfg.n_group,
            topk_group=cfg.topk_group, scaling=cfg.routed_scaling_factor,
            normalize_topk=cfg.norm_topk_prob)
        return moe_grouped.held_rows(idx, cfg.expert_offset,
                                     cfg.experts_held), wts


def moe_prefill(w: Dict, cfg: DeepseekV2Config, x):
    """x (T, C): the picks that fall on held experts, rows sorted by
    expert, plus the shared experts. -> (y (T, C), the picks that fell
    here: the rows that reach the grouped kernel)."""
    idx, wts = route(w, cfg, x)
    with part("experts"):
        here = idx < cfg.experts_held
        y = moe_grouped.moe_grouped_ffn_prefill(
            x, idx, jnp.where(here, wts, 0.0), w["experts.w_gate"],
            w["experts.w_up"], w["experts.w_down"])
    with part("ffn"):
        y = y + _swiglu(_sub(w, "shared_experts."), x)
    with part("router"):
        return y, here.sum(dtype=jnp.int32)


def _is_moe(cfg: DeepseekV2Config, layer: int) -> bool:
    return layer >= cfg.first_k_dense_replace


def block_forward(w: Dict, cfg: DeepseekV2Config, moe: bool, x, cos, sin,
                  cache, start_pos):
    """One decoder block on x (b, s, C), layered: attention in the
    expanded form over ``cache["ckv"]`` (or over the block's own rows
    when ``cache`` is None). -> (x', cache', picks that fell here)."""
    b, s, _ = x.shape
    eps = cfg.rms_norm_eps
    with part("norm"):
        xn = rms_norm(x, w["input_layernorm.weight"], eps)
    y, cache = mla_layered(_sub(w, "self_attn."), cfg, xn, cos, sin, cache,
                           start_pos)
    with part("attn_out"):
        x = x + y
    with part("norm"):
        xn = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    rows = jnp.zeros((), jnp.int32)
    if moe:
        y, rows = moe_prefill(_sub(w, "mlp."), cfg, xn.reshape(b * s, -1))
        y = y.reshape(b, s, -1)
    else:
        with part("ffn"):
            y = _swiglu(_sub(w, "mlp."), xn)
    with part("ffn"):
        return x + y, cache, rows


def hidden_forward(w: Dict, cfg: DeepseekV2Config, ids, cache=None,
                   start_pos=0):
    """ids (b, s) -> (the residual before the final norm (b, s, C),
    cache', the picks that fell on held experts over all expert
    layers)."""
    s = ids.shape[1]
    with part("attn_in"):
        cos, sin = rope_tables(cfg, start_pos + jnp.arange(s))
    with part("embed"):
        x = jnp.take(w["model.embed_tokens.weight"], ids, axis=0)
    new_cache, rows = [], jnp.zeros((), jnp.int32)
    for i in range(cfg.num_layers):
        x, c, r = block_forward(_sub(w, f"model.layers.{i}."), cfg,
                                _is_moe(cfg, i), x, cos, sin,
                                None if cache is None else cache[i],
                                start_pos)
        new_cache.append(c)
        with part("router"):
            rows = rows + r
    return x, (None if cache is None else new_cache), rows


def head_forward(w: Dict, cfg: DeepseekV2Config, h):
    with part("head"):
        return jnp.matmul(rms_norm(h, w["model.norm.weight"],
                                   cfg.rms_norm_eps), w["lm_head.weight"])


def decode_step(w: Dict, cfg: DeepseekV2Config, x, pool, tables, positions):
    """One token a row through every block over the PAGED latent pool:
    x (b, C) embeddings, pool (L, NB, BT, lanes), tables (b, MB),
    positions (b,). A row whose table starts at the scratch block is
    idle: it routes to no expert. -> (the residual (b, C), pool, int32
    (5,): :data:`STEP_COUNTERS`, all but the first summed over the
    expert layers)."""
    eps = cfg.rms_norm_eps
    held, k = cfg.experts_held, cfg.num_experts_per_tok
    active = tables[:, 0] != 0
    with part("attn_in"):
        cos, sin = rope_tables(cfg, positions)              # (b, d_r)
    counts = jnp.zeros(3, jnp.int32)
    n_moe = 0
    for i in range(cfg.num_layers):
        lw = _sub(w, f"model.layers.{i}.")
        with part("norm"):
            xn = rms_norm(x, lw["input_layernorm.weight"], eps)
        y, pool = mla_paged(_sub(lw, "self_attn."), cfg, xn, cos, sin, pool,
                            tables, positions, i)
        with part("attn_out"):
            x = x + y
        with part("norm"):
            xn = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
        mw = _sub(lw, "mlp.")
        if _is_moe(cfg, i):
            idx, wts = route(mw, cfg, xn)
            with part("experts"):
                y = moe_grouped.moe_grouped_ffn_decode(
                    xn, moe_grouped.dense_weights(idx, wts, active, held),
                    mw["experts.w_gate"], mw["experts.w_up"],
                    mw["experts.w_down"])
            with part("ffn"):
                y = y + _swiglu(_sub(mw, "shared_experts."), xn)
            with part("router"):
                counts = counts + moe_grouped.routing_counts(idx, active,
                                                             held)
            n_moe += 1
        else:
            with part("ffn"):
                y = _swiglu(mw, xn)
        with part("ffn"):
            x = x + y
    with part("router"):
        picks = active.sum(dtype=jnp.int32) * (k * n_moe)
    return x, pool, jnp.concatenate([
        jnp.full((1,), n_moe, jnp.int32), counts, picks[None]])


# -------------------------------------------------------------- the layers
class DeepseekV2Gate(nn.Layer):
    """The router over ALL ``n_routed_experts``, whatever is held."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.n_routed_experts),
            default_initializer=init.Normal(0.0, cfg.initializer_range))


class DeepseekV2MoE(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        std = cfg.initializer_range
        self.gate = DeepseekV2Gate(cfg)
        self.experts = GroupedSwiGLUExperts(
            cfg.experts_held, cfg.hidden_size, cfg.moe_intermediate_size,
            initializer_range=std)
        self.shared_experts = Xing4MLP(
            cfg.hidden_size,
            cfg.moe_intermediate_size * cfg.n_shared_experts, std)


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config, moe: bool):
        super().__init__()
        C = cfg.hidden_size
        self.input_layernorm = nn.RMSNorm(C, epsilon=cfg.rms_norm_eps)
        self.self_attn = Xing4Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(C,
                                                   epsilon=cfg.rms_norm_eps)
        self.mlp = (DeepseekV2MoE(cfg) if moe else
                    Xing4MLP(C, cfg.intermediate_size,
                             cfg.initializer_range))


class DeepseekV2Model(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=init.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([
            DeepseekV2DecoderLayer(cfg, _is_moe(cfg, i))
            for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)


class DeepseekV2ForCausalLM(LatentCausalLM):
    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        if cfg.tie_word_embeddings:
            raise ValueError("DeepseekV2ForCausalLM has an untied output "
                             "head")
        self.cfg = cfg
        self.model = DeepseekV2Model(cfg)
        self.lm_head = _proj(cfg.hidden_size, cfg.vocab_size,
                             cfg.initializer_range)
        from paddle_tpu.parallel import mp_layers as mp
        self.loss_fn = mp.ParallelCrossEntropy()

    def forward(self, input_ids, attn_mask=None, cache=None, start_pos=0,
                positions: Optional[jax.Array] = None,
                moe_rows: bool = False):
        """Logits (b, s, vocab) over the rows of the vocabulary held
        here; with ``positions`` (b,) only each row's logits at that
        position, (b, vocab). With ``cache`` also the updated cache; with
        ``moe_rows`` also, last, the picks that fell on held experts
        (int32 scalar: the rows the prefill's grouped kernel was sent)."""
        del attn_mask       # causal; serving pads on the right
        w, cfg = self._weights(), self.cfg
        h, cache, rows = hidden_forward(w, cfg, input_ids, cache, start_pos)
        if positions is not None:
            h = jnp.take_along_axis(h, positions[:, None, None], axis=1)[:, 0]
        out = (head_forward(w, cfg, h),)
        out += () if cache is None else (cache,)
        out += (rows,) if moe_rows else ()
        return out[0] if len(out) == 1 else out

    def fused_decode_plan(self, state, probe=False):
        """What ``serving.ServingEngine`` asks of a model (docs/SERVING.md
        §Architectures the engine takes): ``Xing4ForCausalLM``'s plan,
        with a fifth step counter (``moe_picks``) and a ``prefill_moe``
        whose ``rows`` is ``"counted"``: the prefill program returns the
        picks that fell on held experts, which the wave's shape cannot
        say."""
        if "model.layers.0.self_attn.kv_b_proj.weight" not in state:
            return None     # a quantized or otherwise foreign state
        cfg = self.cfg
        meta = {**latent_plan(cfg, _itemsize(state)),
                "step_counters": STEP_COUNTERS,
                "prefill_moe": {
                    "layers": cfg.num_layers - cfg.first_k_dense_replace,
                    "k": cfg.num_experts_per_tok,
                    "path": moe_grouped.prefill_path(
                        cfg.hidden_size, cfg.moe_intermediate_size),
                    "rows": "counted"}}
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
