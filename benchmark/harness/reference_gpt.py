"""Plain reference for arch ``gpt`` (GPT-2): float32, full precision
matmuls, no cache, no kernels, no batching tricks.

Follows the GPT-2 description (Radford et al. 2019; the
``GPT2LMHeadModel`` of the published checkpoints): learned positions,
pre-LayerNorm blocks with biases, multi-head causal attention, a
``gelu_new`` (tanh) feed-forward of four times the width, a final
LayerNorm and an output head tied to the token embedding. It reads the
run's own weights by their ``state_dict`` names and upcasts them layer by
layer, so it fits beside them on the chip. Sizes come from the
configuration file's published keys, not from the program's config
object.
"""

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """Sizes for ``opcount`` from GPT-2's published keys."""
    h, nh = cfg["n_embd"], cfg["n_head"]
    return dict(h=h, layers=cfg["n_layer"], heads=nh, kv_heads=nh,
                head_dim=h // nh, ffn=4 * h, ffn_mats=2,
                vocab=cfg["vocab_size"], tied=True,
                positions=cfg["n_positions"])


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _layer(x, w, *, n_head, eps):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    b, s, h = x.shape
    hd = h // n_head
    y = _ln(x, w["ln_1.weight"], w["ln_1.bias"], eps)
    qkv = jnp.matmul(y, w["attn.qkv_proj.weight"], precision=_HI) \
        + w["attn.qkv_proj.bias"]
    q, k, v = (t.reshape(b, s, n_head, hd) for t in jnp.split(qkv, 3, -1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HI)
    x = x + jnp.matmul(att.reshape(b, s, h), w["attn.out_proj.weight"],
                       precision=_HI) + w["attn.out_proj.bias"]
    y = _ln(x, w["ln_2.weight"], w["ln_2.bias"], eps)
    y = _gelu_new(jnp.matmul(y, w["fc_in.weight"], precision=_HI)
                  + w["fc_in.bias"])
    return x + jnp.matmul(y, w["fc_out.weight"], precision=_HI) \
        + w["fc_out.bias"]


@jax.jit
def _embed(wte, wpe, ids):
    pos = jnp.arange(ids.shape[1])
    return (jnp.take(wte, ids, axis=0).astype(jnp.float32)
            + wpe[pos].astype(jnp.float32)[None])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, lnw, lnb, wte, *, eps):
    y = _ln(x, lnw.astype(jnp.float32), lnb.astype(jnp.float32), eps)
    return jnp.einsum("...h,vh->...v", y, wte.astype(jnp.float32),
                      precision=_HI)


def hidden(state, ids, cfg):
    """(b, s) token ids -> (b, s, h) float32 before the final norm."""
    x = _embed(state["gpt.wte.weight"], state["gpt.wpe.weight"], ids)
    for i in range(cfg["n_layer"]):
        pre = f"gpt.h.{i}."
        w = {k[len(pre):]: v for k, v in state.items() if k.startswith(pre)}
        x = _layer(x, w, n_head=cfg["n_head"],
                   eps=cfg["layer_norm_epsilon"])
    return x


def logits_at(state, ids, positions, cfg):
    """Reference logits (n, vocab rows) at ``positions`` of one sequence
    ``ids`` (1, s)."""
    x = hidden(state, ids, cfg)[0][positions]
    return _head(x, state["gpt.ln_f.weight"], state["gpt.ln_f.bias"],
                 state["gpt.wte.weight"], eps=cfg["layer_norm_epsilon"])


def loss(state, ids, labels, cfg):
    """Mean next-token cross-entropy of (b, s) ``ids`` against
    ``labels``, the training reference."""
    lg = _head(hidden(state, ids, cfg), state["gpt.ln_f.weight"],
               state["gpt.ln_f.bias"], state["gpt.wte.weight"],
               eps=cfg["layer_norm_epsilon"])
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
