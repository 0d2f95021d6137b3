"""GPT-2 — the single-device end-to-end config (BASELINE config #1).

Capability reference: PaddleNLP's GPT pretrain on the reference substrate
(SURVEY.md §2.7 note). TPU-first choices: pre-norm blocks in bf16-friendly
form, attention through ops.flash_attention (MXU path), learned positional
embeddings, weight-tied unembedding.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as init
from paddle_tpu.profiler.parts import part


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 1024
    intermediate_size: Optional[int] = None
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True

    # gpt2-345m preset
    @property
    def num_kv_heads(self):
        """MHA: kv heads == heads (llama-shaped accessors for shared
        roofline/cache math)."""
        return self.num_heads

    @classmethod
    def gpt2_medium(cls):
        return cls(hidden_size=1024, num_layers=24, num_heads=16)

    @classmethod
    def tiny(cls, vocab_size=1024):
        return cls(vocab_size=vocab_size, hidden_size=128, num_layers=2,
                   num_heads=4, max_position_embeddings=128,
                   hidden_dropout_prob=0.0, attention_dropout_prob=0.0)

    @property
    def ffn_size(self):
        return self.intermediate_size or 4 * self.hidden_size


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_heads
        w_init = init.Normal(0.0, cfg.initializer_range)
        self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=w_init)
        self.out_proj = nn.Linear(h, h, weight_attr=init.Normal(
            0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)))
        self.num_heads = nh
        self.head_dim = h // nh
        self.attn_dropout = cfg.attention_dropout_prob

    def forward(self, x, cache=None, start_pos=0):
        b, s, h = x.shape
        with part("attn_in"):
            qkv = self.qkv_proj(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, self.num_heads, self.head_dim)
            k = k.reshape(b, s, self.num_heads, self.head_dim)
            v = v.reshape(b, s, self.num_heads, self.head_dim)
        if cache is not None:
            # decode: append at [start_pos, start_pos+s), attend the
            # filled prefix (position-masked static buffers)
            with part("attn"):
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), start_pos,
                    axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), start_pos,
                    axis=1)
                if (isinstance(start_pos, int)
                        and start_pos + s == k_cache.shape[1]):
                    # a prefill that fills its cache to the end: the fill
                    # mask IS the bottom-right causal edge (llama.py)
                    out = F.scaled_dot_product_attention(
                        q, k_cache, v_cache, is_causal=True)
                else:
                    q_pos = start_pos + jnp.arange(s)[:, None]
                    k_pos = jnp.arange(k_cache.shape[1])[None, :]
                    mask = (k_pos <= q_pos)[None, None]
                    out = F.scaled_dot_product_attention(
                        q, k_cache, v_cache, attn_mask=mask,
                        is_causal=False)
            with part("attn_out"):
                out = self.out_proj(out.reshape(b, s, h))
            return out, {"k": k_cache, "v": v_cache}
        with part("attn"):
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.attn_dropout,
                training=self.training)
        with part("attn_out"):
            return self.out_proj(out.reshape(b, s, h))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        w_init = init.Normal(0.0, cfg.initializer_range)
        self.fc_in = nn.Linear(cfg.hidden_size, cfg.ffn_size, weight_attr=w_init)
        self.fc_out = nn.Linear(cfg.ffn_size, cfg.hidden_size,
                                weight_attr=init.Normal(
                                    0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)))
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, start_pos=0):
        with part("norm"):
            xn = self.ln_1(x)
        new_cache = None
        if cache is not None:       # serving: no dropout
            attn, new_cache = self.attn(xn, cache=cache, start_pos=start_pos)
        else:
            attn = self.attn(xn)
        drop = self.dropout if cache is None else (lambda y: y)
        with part("attn_out"):
            x = x + drop(attn)
        with part("norm"):
            xn = self.ln_2(x)
        with part("ffn"):
            x = x + drop(self.fc_out(F.gelu(self.fc_in(xn),
                                            approximate=True)))
        return x if cache is None else (x, new_cache)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        w_init = init.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size, weight_attr=w_init)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                weight_attr=w_init)
        self.drop = nn.Dropout(cfg.hidden_dropout_prob)
        self.h = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids, cache=None, start_pos=0):
        b, s = input_ids.shape
        pos = (start_pos + jnp.arange(s))[None, :]
        with part("embed"):
            x = self.wte(input_ids) + self.wpe(pos)
            if cache is None:
                x = self.drop(x)
        new_cache = []
        for i, block in enumerate(self.h):
            if cache is None:
                x = block(x)
            else:
                x, c = block(x, cache=cache[i], start_pos=start_pos)
                new_cache.append(c)
        with part("head"):
            x = self.ln_f(x)
        return x if cache is None else (x, new_cache)


class GPTPretrainModel(nn.Layer):
    """LM head (tied) + causal LM loss."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)
        self.cfg = cfg
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, cache=None, start_pos=0):
        if cache is not None:
            x, new_cache = self.gpt(input_ids, cache=cache,
                                    start_pos=start_pos)
        else:
            x = self.gpt(input_ids)
        with part("head"):
            if self.cfg.tie_word_embeddings:
                logits = jnp.matmul(x, self.gpt.wte.weight.T)
            else:
                logits = self.lm_head(x)
        if cache is not None:
            return logits, new_cache
        return logits

    def init_cache(self, batch_size, max_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        shape = (batch_size, max_len, cfg.num_heads,
                 cfg.hidden_size // cfg.num_heads)
        return [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                for _ in range(cfg.num_layers)]

    def fused_decode_plan(self, state, probe=False):
        """Fused decode-step plan, GPT block variant (ops.fused_decode
        arch='gpt' — LayerNorm+bias, MHA, learned positions, GELU): the
        architecture the reference's fused_multi_transformer serves."""
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        if hd % 2 or "gpt.h.0.attn.qkv_proj.weight" not in state:
            return None
        meta = {
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_heads,
            "head_dim": hd, "eps": cfg.layer_norm_epsilon,
            "rope_base": 10000.0, "arch": "gpt",
        }
        if probe:
            return meta
        from paddle_tpu.ops import fused_decode as fd
        from paddle_tpu.nn.functional import layer_norm as _ln
        params = fd.build_fused_params_gpt(state, cfg.num_layers)
        wte = state["gpt.wte.weight"]
        wpe = state["gpt.wpe.weight"]
        lnf_w = state["gpt.ln_f.weight"]
        lnf_b = state["gpt.ln_f.bias"]
        def embed(tok, pos):                  # (b,), scalar -> (b, h)
            with part("embed"):
                return jnp.take(wte, tok, axis=0) + wpe[pos]

        def head(x):
            with part("head"):
                xn = _ln(x, (x.shape[-1],), lnf_w, lnf_b,
                         cfg.layer_norm_epsilon)
                if cfg.tie_word_embeddings:
                    from paddle_tpu.ops import tied_unembed
                    return tied_unembed(xn, wte)
                return jnp.dot(xn, state["lm_head.weight"])

        return dict(meta, params=params, embed=embed, head=head)

    def loss(self, logits, labels):
        return F.cross_entropy(logits.reshape(-1, logits.shape[-1]),
                               labels.reshape(-1))

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for _, p in self.named_parameters())

    def pipeline_parts(self):
        """Factor for the SPMD pipeline (parallel.pipeline). Tied embeddings
        use the pipeline's tied_head path (SharedLayerDesc parity): the head
        unembeds with the embed stage's wte weight."""
        from paddle_tpu.nn.layer import functional_call
        from paddle_tpu.parallel.pipeline import PipelineParts, part_specs

        tied = self.cfg.tie_word_embeddings
        embed = _GPTEmbed(self.gpt.wte, self.gpt.wpe, self.gpt.drop)
        blocks = list(self.gpt.h)
        template = blocks[0]
        ln_f = self.gpt.ln_f
        model_loss = self.loss

        def embed_apply(st, ids):
            return functional_call(embed, st, ids)

        def block_apply(st, h):
            return functional_call(template, st, h)

        if tied:
            def head_apply(head_st, embed_st, h, labels):
                x = functional_call(ln_f, head_st, h)
                logits = jnp.matmul(x, embed_st["wte.weight"].T)
                return model_loss(logits, labels)

            head_state = ln_f.trainable_state()
            head_pspecs = part_specs(ln_f)
        else:
            head = _GPTHead(ln_f, self.lm_head, model_loss)

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


class _GPTEmbed(nn.Layer):
    def __init__(self, wte, wpe, drop):
        super().__init__()
        self.wte, self.wpe, self.drop = wte, wpe, drop

    def forward(self, ids):
        pos = jnp.arange(ids.shape[1])[None, :]
        return self.drop(self.wte(ids) + self.wpe(pos))


class _GPTHead(nn.Layer):
    def __init__(self, ln_f, lm_head, loss_fn):
        super().__init__()
        self.ln_f, self.lm_head = ln_f, lm_head
        self.loss_fn = loss_fn       # the model's own .loss — one definition

    def forward(self, h, labels):
        return self.loss_fn(self.lm_head(self.ln_f(h)), labels)
