"""Llama family — the hybrid-parallel flagship (BASELINE configs #2).

Capability reference: PaddleNLP's Llama pretrain runs on the reference
substrate via Fleet hybrid parallel (SURVEY.md §2.7 note, §6 config matrix:
"Llama-2 7B/65B hybrid mp×pp×sharding-2").

TPU-first choices:
* attention + MLP built from the tensor-parallel layers (parallel/mp_layers):
  q/k/v/gate/up are column-parallel, o/down are row-parallel, the embedding is
  vocab-parallel — on a 1-device mesh they degrade to dense layers, so one
  implementation serves tests, single-chip and the full mesh.
* GQA (num_kv_heads < num_heads) with head counts divisible by the mp degree.
* RoPE via ops.rope (XLA fuses the rotation into the attention matmuls),
  RMSNorm via ops.rms_norm (Pallas on TPU), attention via
  F.scaled_dot_product_attention (Pallas flash path on TPU).
* weights default to the reference's init (normal(0, initializer_range)).
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as init
from paddle_tpu.ops import rope as rope_ops
from paddle_tpu.parallel import mp_layers as mp
from paddle_tpu.profiler.parts import part


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None      # None → MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_base: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # sequence-parallel activations between TP regions (Megatron-SP)
    sequence_parallel: bool = False
    # long-context strategy over the 'sep' mesh axis: None | 'ring' | 'ulysses'
    context_parallel: Optional[str] = None
    # per-layer activation recompute in the no-cache (training) forward
    recompute: bool = False
    # reference recompute_granularity (fleet recompute): what gets
    # RECOMPUTED in backward. 'full' = the whole layer (boundaries only
    # saved — max memory saving, ~fwd/3 extra FLOPs); 'full_attn' = the
    # attention block (projection/FFN matmul outputs saved); 'core_attn' =
    # only softmax(qk)v (q/k/v saved too — min recompute: the flash
    # kernel's fwd replay for its LSE residual is the only matmul re-run)
    recompute_granularity: str = "full"
    # train_loss(): compute the final norm→unembed→CE in this many
    # sequence chunks under remat (the full (b, s, vocab) logits tensor
    # never materializes); 1 = plain head+loss
    loss_seq_chunks: int = 1
    # Mistral-style causal sliding-window attention (None = full causal).
    # Rides the flash kernel's window_size support in training; the decode
    # path masks the KV cache to the last `sliding_window` positions.
    sliding_window: Optional[int] = None

    @classmethod
    def mistral_7b(cls):
        # 4096-key window over a 32k context (the published pairing — a
        # window equal to max positions would never mask anything)
        return cls(vocab_size=32000, hidden_size=4096,
                   intermediate_size=14336, num_layers=32, num_heads=32,
                   num_kv_heads=8, max_position_embeddings=32768,
                   sliding_window=4096)

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, vocab_size=256):
        return cls(vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2,
                   max_position_embeddings=128)

    @classmethod
    def llama2_7b(cls):
        return cls()

    @classmethod
    def llama2_13b(cls):
        return cls(hidden_size=5120, intermediate_size=13824, num_layers=40,
                   num_heads=40)

    @classmethod
    def llama_65b(cls):
        """Llama-65B shape (BASELINE config #2 north-star scale)."""
        return cls(hidden_size=8192, intermediate_size=22016, num_layers=80,
                   num_heads=64, max_position_embeddings=2048)

    @classmethod
    def llama2_70b(cls):
        return cls(hidden_size=8192, intermediate_size=28672, num_layers=80,
                   num_heads=64, num_kv_heads=8)


def _tp_classes(cfg: LlamaConfig):
    """Column/row TP layer classes, SP variants when sequence_parallel."""
    if cfg.sequence_parallel:
        return mp.ColumnSequenceParallelLinear, mp.RowSequenceParallelLinear
    return mp.ColumnParallelLinear, mp.RowParallelLinear


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                          cfg.head_dim)
        w = init.Normal(0.0, cfg.initializer_range)
        col, row = _tp_classes(cfg)
        self.q_proj = col(h, nh * hd, weight_attr=w, has_bias=False,
                          gather_output=False)
        self.k_proj = col(h, nkv * hd, weight_attr=w, has_bias=False,
                          gather_output=False)
        self.v_proj = col(h, nkv * hd, weight_attr=w, has_bias=False,
                          gather_output=False)
        self.o_proj = row(nh * hd, h, weight_attr=init.Normal(
            0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)),
            has_bias=False, input_is_parallel=True)
        self.cfg = cfg

    def forward(self, x, cos=None, sin=None, attn_mask=None, cache=None,
                start_pos=0):
        cfg = self.cfg
        b, s, _ = x.shape
        with part("attn_in"):
            q = self.q_proj(x).reshape(b, s, cfg.num_heads, cfg.head_dim)
            k = self.k_proj(x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
            v = self.v_proj(x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
            if cos is None or sin is None:
                pos = start_pos + jnp.arange(s)
                cos, sin = rope_ops.rope_cos_sin(s, cfg.head_dim,
                                                 base=cfg.rope_base,
                                                 position_ids=pos)
            q = rope_ops.apply_rotary_pos_emb(q, cos, sin)
            k = rope_ops.apply_rotary_pos_emb(k, cos, sin)
        with part("attn"):
            out, cache = self._attend(q, k, v, attn_mask, cache, start_pos)
        with part("attn_out"):
            out = self.o_proj(out.reshape(b, s,
                                          cfg.num_heads * cfg.head_dim))
        return out if cache is None else (out, cache)

    def _attend(self, q, k, v, attn_mask, cache, start_pos):
        """The attention itself on projected q, k, v (b, s, heads, d) ->
        (out (b, s, num_heads, d), the cache with k and v written or
        None)."""
        cfg = self.cfg
        s = q.shape[1]
        if cache is not None:
            # decode: write k/v at [start_pos, start_pos+s), attend to the
            # filled prefix (static max length, position-masked)
            import jax as _jax
            k_cache = _jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), start_pos, axis=1)
            v_cache = _jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), start_pos, axis=1)
            max_len = k_cache.shape[1]
            if isinstance(start_pos, int) and start_pos + s == max_len:
                # a prefill that fills its cache to the end (a serving
                # wave): the fill mask IS the bottom-right causal edge,
                # which the flash kernels walk without streaming a mask
                out = F.scaled_dot_product_attention(
                    q, k_cache, v_cache, is_causal=True,
                    window_size=cfg.sliding_window)
                return out, {"k": k_cache, "v": v_cache}
            q_pos = start_pos + jnp.arange(s)[:, None]          # (s, 1)
            k_pos = jnp.arange(max_len)[None, :]                 # (1, max)
            mask = (k_pos <= q_pos)[None, None]                  # causal+fill
            if cfg.sliding_window is not None:
                mask = mask & (k_pos > q_pos - cfg.sliding_window)[None, None]
            out = F.scaled_dot_product_attention(
                q, k_cache, v_cache, attn_mask=mask, is_causal=False)
            return out, {"k": k_cache, "v": v_cache}
        if cfg.context_parallel:
            if cfg.sliding_window is not None:
                raise ValueError(
                    "sliding_window is not supported on the "
                    "context_parallel path (the ring/Ulysses kernels "
                    "attend the full causal context) — silent full-causal "
                    "training would mismatch the windowed decode")
            from paddle_tpu.parallel.context_parallel import (
                context_parallel_attention)
            out = context_parallel_attention(q, k, v, axis="sep",
                                             mode=cfg.context_parallel)
        else:
            # named for the recompute_granularity save policies
            from jax.ad_checkpoint import checkpoint_name
            q = checkpoint_name(q, "attn_qkv")
            k = checkpoint_name(k, "attn_qkv")
            v = checkpoint_name(v, "attn_qkv")
            # always causal; an attn_mask (e.g. padding) composes with it
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=True,
                window_size=cfg.sliding_window)
            out = checkpoint_name(out, "attn_out")
        return out, None


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, ffn = cfg.hidden_size, cfg.intermediate_size
        w = init.Normal(0.0, cfg.initializer_range)
        col, row = _tp_classes(cfg)
        self.gate_proj = col(h, ffn, weight_attr=w, has_bias=False,
                             gather_output=False)
        self.up_proj = col(h, ffn, weight_attr=w, has_bias=False,
                           gather_output=False)
        self.down_proj = row(ffn, h, weight_attr=init.Normal(
            0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)),
            has_bias=False, input_is_parallel=True)

    def forward(self, x):
        from jax.ad_checkpoint import checkpoint_name
        g = checkpoint_name(self.gate_proj(x), "ffn_gate")
        u = checkpoint_name(self.up_proj(x), "ffn_up")
        return self.down_proj(F.silu(g) * u)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cos=None, sin=None, attn_mask=None, cache=None,
                start_pos=0):
        with part("norm"):
            xn = self.input_layernorm(x)
        new_cache = None
        if cache is not None:
            attn, new_cache = self.self_attn(xn, cos, sin, attn_mask,
                                             cache=cache,
                                             start_pos=start_pos)
        else:
            attn = self.self_attn(xn, cos, sin, attn_mask)
        with part("attn_out"):
            x = x + attn
        with part("norm"):
            xn = self.post_attention_layernorm(x)
        with part("ffn"):
            x = x + self.mlp(xn)
        return x if cache is None else (x, new_cache)


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = mp.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=init.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([LlamaDecoderLayer(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, cache=None, start_pos=0):
        cfg = self.cfg
        s = input_ids.shape[1]
        pos = start_pos + jnp.arange(s) if cache is not None else None
        with part("attn_in"):
            cos, sin = rope_ops.rope_cos_sin(s, cfg.head_dim,
                                             base=cfg.rope_base,
                                             position_ids=pos)
        with part("embed"):
            x = self.embed_tokens(input_ids)
        if cache is not None:
            new_cache = []
            for i, layer in enumerate(self.layers):
                x, c = layer(x, cos, sin, attn_mask, cache=cache[i],
                             start_pos=start_pos)
                new_cache.append(c)
            with part("head"):
                return self.norm(x), new_cache
        if cfg.recompute:
            # per-layer activation recompute (reference: fleet per-layer
            # recompute, fleet/meta_parallel recompute_hybrid). The
            # granularity maps to a named-save policy: 'full' saves only
            # layer boundaries; 'full_attn'/'core_attn' additionally save
            # the big matmul outputs so backward re-runs only the cheap
            # elementwise ops (+ the attention core for 'full_attn').
            from jax.ad_checkpoint import checkpoint_policies as cp
            gran = cfg.recompute_granularity
            # attn_out is deliberately NOT saved: the flash kernel's
            # backward replays its forward for the LSE residual anyway,
            # which reproduces the output — saving it would spend
            # b·s·h bytes/layer for nothing
            if gran == "full":
                policy = None
            elif gran == "full_attn":
                policy = cp.save_only_these_names("ffn_gate", "ffn_up")
            elif gran == "core_attn":
                policy = cp.save_only_these_names(
                    "attn_qkv", "ffn_gate", "ffn_up")
            else:
                raise ValueError(
                    f"unknown recompute_granularity {gran!r}; expected "
                    "'full', 'full_attn' or 'core_attn'")
            for layer in self.layers:
                x = jax.checkpoint(
                    lambda t, _l=layer: _l(t, cos, sin, attn_mask),
                    policy=policy)(x)
        else:
            for layer in self.layers:
                x = layer(x, cos, sin, attn_mask)
        with part("head"):
            return self.norm(x)


class CausalLMBase(nn.Layer):
    """Shared scaffolding for decoder-only LMs built on `.model` (with
    embed_tokens/layers/norm), `.lm_head` and `.loss_fn` attributes."""

    def init_cache(self, batch_size, max_len, dtype=jnp.bfloat16):
        """Preallocated KV cache: one {'k','v'} buffer pair per layer."""
        cfg = self.cfg
        shape = (batch_size, max_len, cfg.kv_heads, cfg.head_dim)
        return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                for _ in range(cfg.num_layers)]

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for _, p in self.named_parameters())

    def train_loss(self, input_ids, labels, attn_mask=None):
        """Fused forward + LM loss. With ``cfg.loss_seq_chunks > 1`` the
        final norm→unembed→cross-entropy runs in sequence chunks under
        remat, so the full (b, s, vocab) logits tensor never exists — the
        TPU analog of the reference's fused head/loss kernels
        (fused_linear_param_grad_add + _c_softmax_with_cross_entropy):
        at 32k vocab the logits are the single largest training
        activation (0.5-1 GiB at b4 s2048), and chunking trades them for
        a per-chunk lm_head replay in backward (~1% of step FLOPs)."""
        chunks = getattr(self.cfg, "loss_seq_chunks", 1)
        x = self.model(input_ids, attn_mask)
        aux = jnp.zeros((), jnp.float32)
        if isinstance(x, tuple):      # MoE bodies return (hidden, aux)
            x, aux = x
            aux = getattr(self.cfg, "aux_loss_weight", 1.0) * aux
        if chunks <= 1:
            logits = self._unembed(x)
            with part("loss"):
                return self.loss_fn(logits, labels, reduction="mean") + aux
        b, s, h = x.shape
        if s % chunks:
            raise ValueError(
                f"loss_seq_chunks={chunks} does not divide seq {s}")
        sc = s // chunks
        xc = jnp.moveaxis(x.reshape(b, chunks, sc, h), 1, 0)
        lc = jnp.moveaxis(labels.reshape(b, chunks, sc), 1, 0)
        ignore = getattr(self.loss_fn, "ignore_index", -100)

        @jax.checkpoint
        def chunk_sums(x_c, l_c):
            logits = self._unembed(x_c)
            with part("loss"):
                nll = self.loss_fn(logits, l_c, reduction="none")
                return jnp.sum(nll), jnp.sum(l_c != ignore)

        def body(carry, xs):
            loss_sum, cnt = carry
            a, n = chunk_sums(*xs)
            with part("loss"):
                return (loss_sum + a, cnt + n), None

        (loss_sum, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (xc, lc))
        with part("loss"):
            return loss_sum / jnp.maximum(cnt, 1) + aux

    def _unembed(self, x):
        with part("head"):
            if getattr(self.cfg, "tie_word_embeddings", False):
                from paddle_tpu.parallel import mp_layers as _mp
                logits = jnp.matmul(x, self.model.embed_tokens.weight.T)
                return _mp.constrain(logits,
                                     _mp._last_dim_spec(_mp.MP_AXIS))
            return self.lm_head(x)

    def _pipeline_block_apply(self, template):
        """(one_block_state, h) -> h, built over `template`. Subclasses with
        per-block extra losses return (h, extra) instead."""
        from paddle_tpu.nn.layer import functional_call
        cfg = self.cfg

        def block_apply(st, h):
            s = h.shape[1]
            cos, sin = rope_ops.rope_cos_sin(s, cfg.head_dim,
                                             base=cfg.rope_base)
            return functional_call(template, st, h, cos, sin, None)

        return block_apply

    def pipeline_parts(self):
        """Factor the model for the SPMD pipeline schedule
        (parallel.pipeline.make_pipeline_train_step). Tied embeddings ride
        the pipeline's tied_head path (SharedLayerDesc parity)."""
        from paddle_tpu.nn.layer import functional_call
        from paddle_tpu.parallel.pipeline import PipelineParts, part_specs

        tied = self.cfg.tie_word_embeddings
        embed = self.model.embed_tokens
        blocks = list(self.model.layers)
        template = blocks[0]
        block_apply = self._pipeline_block_apply(template)

        def embed_apply(st, ids):
            return functional_call(embed, st, ids)

        if tied:
            norm = self.model.norm
            loss_fn = self.loss_fn

            def head_apply(head_st, embed_st, h, labels):
                x = functional_call(norm, head_st, h)
                logits = jnp.matmul(x, embed_st["weight"].T)
                logits = mp.constrain(logits, mp._last_dim_spec(mp.MP_AXIS))
                return loss_fn(logits, labels, reduction="mean")

            head_state = norm.trainable_state()
            head_pspecs = part_specs(norm)
        else:
            head = _LMHead(self.model.norm, self.lm_head, self.loss_fn)

            def head_apply(st, h, labels):
                return functional_call(head, st, h, labels)

            head_state = head.trainable_state()
            head_pspecs = part_specs(head)

        return PipelineParts(
            embed_state=embed.trainable_state(),
            embed_apply=embed_apply,
            block_states=[b.trainable_state() for b in blocks],
            block_apply=block_apply,
            head_state=head_state,
            head_apply=head_apply,
            embed_pspecs=part_specs(embed),
            block_pspecs=part_specs(template),
            head_pspecs=head_pspecs,
            tied_head=tied,
        )


class LlamaForCausalLM(CausalLMBase):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            # vocab-sharded logits stay sharded into the parallel loss —
            # never materialize a replicated (b, s, vocab) activation
            self.lm_head = mp.ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size,
                weight_attr=init.Normal(0.0, cfg.initializer_range),
                has_bias=False, gather_output=False)
        self.loss_fn = mp.ParallelCrossEntropy()

    def forward(self, input_ids, attn_mask=None, cache=None, start_pos=0):
        if cache is not None:
            x, new_cache = self.model(input_ids, attn_mask, cache=cache,
                                      start_pos=start_pos)
            return self._unembed(x), new_cache
        x = self.model(input_ids, attn_mask)
        return self._unembed(x)    # _unembed: CausalLMBase

    def fused_decode_plan(self, state, probe=False):
        """Plan for the fused decode-step path (ops.fused_decode — the
        fused_multi_transformer analog): stacked per-layer weights plus
        embed/head closures, or None when this config can't ride it
        (active TP mesh, odd head_dim). Weight-only-int8 states build the
        int8 variant (fused_multi_transformer_int8 analog).

        With probe=True only eligibility + static meta are computed (no
        device work) — generate() probes before jit and builds the real
        plan from the traced state inside the jitted program."""
        from paddle_tpu.parallel.mp_layers import _active_mesh
        cfg = self.cfg
        if (_active_mesh(mp.MP_AXIS) is not None or cfg.head_dim % 2
                or cfg.sliding_window is not None):
            # sliding-window decode masks the cache; the fused kernel
            # attends the full filled prefix — scan path serves it
            return None
        int8 = "model.layers.0.self_attn.q_proj.weight_q" in state
        if not int8 and "model.layers.0.self_attn.q_proj.weight" not in state:
            return None     # non-standard state
        from paddle_tpu.ops import fused_decode as fd
        hd = cfg.head_dim
        dq = cfg.num_heads * hd
        blocks = fd.decode_block_plan(
            cfg.hidden_size, dq + 2 * cfg.kv_heads * hd, dq, hd,
            cfg.intermediate_size, wbytes=1 if int8 else 2)
        meta = {
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "eps": cfg.rms_norm_eps,
            "rope_base": cfg.rope_base, "blocks": blocks,
        }
        if probe:
            return meta
        from paddle_tpu.ops.rms_norm import rms_norm
        params = fd.build_fused_params(state, cfg.num_layers,
                                       ffn_pad=blocks["ffn_pad"])
        embed_w = state["model.embed_tokens.weight"]
        norm_w = state["model.norm.weight"]

        def embed(tok, pos):                  # (b,), scalar -> (b, h)
            del pos                           # rope positions, not learned
            with part("embed"):
                return jnp.take(embed_w, tok, axis=0)

        if cfg.tie_word_embeddings:
            from paddle_tpu.ops import tied_unembed
            head_mm = lambda xn: tied_unembed(xn, embed_w)
        elif int8 and "lm_head.weight_q" in state:
            from paddle_tpu.quantization import weight_only_linear
            head_mm = lambda xn: weight_only_linear(
                xn, state["lm_head.weight_q"], state["lm_head.weight_scale"])
        else:
            head_mm = lambda xn: jnp.dot(xn, state["lm_head.weight"])

        def head(x):                          # (b, h) -> (b, vocab)
            with part("head"):
                return head_mm(rms_norm(x, norm_w, cfg.rms_norm_eps))

        return dict(meta, params=params, embed=embed, head=head)

    def loss(self, logits, labels):
        # reduction='mean' divides by the count of non-ignored labels
        return self.loss_fn(logits, labels, reduction="mean")


class _LMHead(nn.Layer):
    """Final norm + unembedding + mean parallel-CE loss (pipeline tail)."""

    def __init__(self, norm, lm_head, loss_fn):
        super().__init__()
        self.norm = norm
        self.lm_head = lm_head
        self.loss_fn = loss_fn

    def forward(self, h, labels):
        logits = self.lm_head(self.norm(h))
        return self.loss_fn(logits, labels, reduction="mean")
