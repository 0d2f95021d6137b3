"""Plain reference for arch ``llama`` (the decoder InternLM2 publishes):
float32, full precision matmuls, no cache, no kernels.

Pre-RMSNorm blocks without biases, grouped-query causal attention with
rotary positions in the rotate-half convention (the one the published
``modeling_internlm2.py`` and ``LlamaForCausalLM`` use), a SwiGLU
feed-forward, a final RMSNorm and an untied output head. The published
InternLM2 checkpoint packs q, k and v into one ``wqkv`` matrix; separate
q, k and v matrices are the same mathematics (configuration file,
``assumed``). Weights are read by the run's ``state_dict`` names and
upcast layer by layer. Sizes come from the configuration file's
published keys.
"""

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """Sizes for ``opcount`` from the llama-style published keys."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(h=h, layers=cfg["num_hidden_layers"], heads=nh,
                kv_heads=cfg["num_key_value_heads"], head_dim=h // nh,
                ffn=cfg["intermediate_size"], ffn_mats=3,
                vocab=cfg["vocab_size"],
                tied=bool(cfg.get("tie_word_embeddings", False)),
                positions=0)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (b, s, heads, hd): rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit,
                   static_argnames=("n_head", "n_kv", "eps", "theta"))
def _layer(x, w, *, n_head, n_kv, eps, theta):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, s, h = x.shape
    hd = h // n_head
    y = _rms(x, w["input_layernorm.weight"], eps)
    q = jnp.matmul(y, w["self_attn.q_proj.weight"], precision=_HI)
    k = jnp.matmul(y, w["self_attn.k_proj.weight"], precision=_HI)
    v = jnp.matmul(y, w["self_attn.v_proj.weight"], precision=_HI)
    q = _rope(q.reshape(b, s, n_head, hd), theta)
    k = _rope(k.reshape(b, s, n_kv, hd), theta)
    v = v.reshape(b, s, n_kv, hd)
    k = jnp.repeat(k, n_head // n_kv, axis=2)
    v = jnp.repeat(v, n_head // n_kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HI)
    x = x + jnp.matmul(att.reshape(b, s, h), w["self_attn.o_proj.weight"],
                       precision=_HI)
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    g = jnp.matmul(y, w["mlp.gate_proj.weight"], precision=_HI)
    u = jnp.matmul(y, w["mlp.up_proj.weight"], precision=_HI)
    return x + jnp.matmul(jax.nn.silu(g) * u, w["mlp.down_proj.weight"],
                          precision=_HI)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    y = _rms(x, norm_w.astype(jnp.float32), eps)
    return jnp.matmul(y, head_w.astype(jnp.float32), precision=_HI)


def hidden(state, ids, cfg):
    """(b, s) token ids -> (b, s, h) float32 before the final norm."""
    x = jnp.take(state["model.embed_tokens.weight"], ids,
                 axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in state.items() if k.startswith(pre)}
        x = _layer(x, w, n_head=cfg["num_attention_heads"],
                   n_kv=cfg["num_key_value_heads"], eps=cfg["rms_norm_eps"],
                   theta=float(cfg["rope_theta"]))
    return x


def logits_at(state, ids, positions, cfg):
    """Reference logits (n, vocab) at ``positions`` of one sequence
    ``ids`` (1, s)."""
    x = hidden(state, ids, cfg)[0][positions]
    return _head(x, state["model.norm.weight"], state["lm_head.weight"],
                 eps=cfg["rms_norm_eps"])
