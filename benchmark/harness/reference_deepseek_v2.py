"""Plain reference for arch ``deepseek_v2`` (DeepSeek-V2, arXiv:2405.04434)
as ONE chip's share of an expert-parallel deployment: float32, full
precision matmuls, no cache, no kernels, importing nothing of the
program.

Per token, hidden ``x``, no bias anywhere, SiLU::

    x = x + MLA(RMSNorm(x));   x = x + FFN(RMSNorm(x))

then a final RMSNorm and an untied head over the rows of the vocabulary
that are held here. MLA is ``reference_xing4.py``'s, with these sizes:
``c_q = RMSNorm(x W_qa)``, ``[q_n | q_r] = c_q W_qb`` a head, ``[c_kv |
k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, rope at YaRN frequencies on
``q_r`` and the one shared ``k_r`` (half-split pairs), ``[k_n | v] =
c_kv W_kvb`` a head, scores ``(q_n k_n + q_r k_r) (d_n + d_r)^-1/2 m^2``
with ``m = 0.1 mscale_all_dim ln(factor) + 1``, causal softmax, ``W_o``.
The FFN of the leading dense layers is SwiGLU; elsewhere
``group_limited_greedy``: ``s = softmax(x W_g)`` over ALL
``router_experts``, a group's score is the largest ``s`` of its
``router_experts / n_group`` consecutive experts, the ``topk_group``
best groups are kept and ``s`` set to 0 outside them, the top
``num_experts_per_tok`` of what is left are picked, weights
``routed_scaling_factor x s`` (normalised first only where
``norm_topk_prob``), plus the shared experts as one SwiGLU of width
``n_shared_experts x moe_intermediate_size``; no token is dropped.

**The share.** ``n_routed_experts`` experts are held here, the router's
ids ``expert_offset .. expert_offset + n_routed_experts - 1``. The
router keeps its width, its groups and its picks; the layer's result
here is the sum over the picks that fall on HELD experts plus the shared
experts, and that goes on to the next layer. Nothing stands in for the
other chips. ``layer_parts`` gives the routed and the shared part of one
layer apart, for the test that adds the shares up.

Weights are read by the run's ``state_dict`` names and upcast as they
are used: a layer at a time, the held experts one at a time (a scan over
the expert axis). Attention scores are taken in blocks of query rows.

**What ``correct`` holds a served request to** is what
``reference_xing4.py``'s docstring says, with this router's ties: a
token's ``decidedness`` is the least, over the expert layers, of the gap
between the ``topk_group``-th and the next group score and the gap
between the k-th and the (k+1)-th kept score. ``logits_at`` applies the
configuration's ``reference_agreement`` to the most decided rows and
hands back non-finite logits where a request breaks it.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import log
from .reference_xing4 import (_attention, _group, _head, _mm, _rms, _swiglu,
                              agreement)

# the fields reference_xing4's MLA reads, then the router's
Sizes = collections.namedtuple(
    "Sizes", "heads d_n d_r d_v d_c eps theta factor orig_max beta_fast "
             "beta_slow mscale mscale_all top_k scaling norm_topk groups "
             "top_groups offset")


def sizes(cfg: dict) -> Sizes:
    rs = cfg["rope_scaling"]
    return Sizes(
        heads=cfg["num_attention_heads"], d_n=cfg["qk_nope_head_dim"],
        d_r=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_c=cfg["kv_lora_rank"], eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), factor=float(rs["factor"]),
        orig_max=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs.get("mscale", 1)),
        mscale_all=float(rs.get("mscale_all_dim", 0)),
        top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), groups=cfg["n_group"],
        top_groups=cfg["topk_group"], offset=int(cfg.get("expert_offset", 0)))


def dims(cfg: dict) -> dict:
    """Sizes for ``opcount_xing4`` and ``opcount_xing4_prefill``, from
    the published keys: ``experts`` is the count HELD here (what a step
    or a wave can touch), ``vocab`` the rows held, and there are no
    residual streams to mix."""
    dense = cfg["first_k_dense_replace"]
    return dict(
        h=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        dense_layers=dense, moe_layers=cfg["num_hidden_layers"] - dense,
        heads=cfg["num_attention_heads"], d_n=cfg["qk_nope_head_dim"],
        d_r=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_c=cfg["kv_lora_rank"], d_q=cfg["q_lora_rank"],
        cache_lanes=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
        ffn=cfg["intermediate_size"], expert_ffn=cfg["moe_intermediate_size"],
        experts=cfg["n_routed_experts"], router_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        streams=0, vocab=cfg["vocab_size"], tied=False, positions=0)


def route(x, gate_w, z: Sizes):
    """x (T, C), gate_w (C, router_experts) -> (the picks' weights as a
    dense (T, router_experts) matrix, zero where an expert was not
    picked; each token's gap to a routing tie (T,))."""
    s = jax.nn.softmax(_mm(x, gate_w), axis=-1)
    t, e = s.shape
    best = s.reshape(t, z.groups, e // z.groups).max(-1)
    gtop, gidx = jax.lax.top_k(best, min(z.top_groups + 1, z.groups))
    gap = (gtop[:, z.top_groups - 1] - gtop[:, z.top_groups]
           if z.groups > z.top_groups else jnp.full((t,), jnp.inf))
    kept = jax.nn.one_hot(gidx[:, :z.top_groups], z.groups,
                          dtype=jnp.float32).sum(1)
    left = s * jnp.repeat(kept, e // z.groups, axis=1)
    top, chosen = jax.lax.top_k(left, z.top_k + 1)
    gap = jnp.minimum(gap, top[:, z.top_k - 1] - top[:, z.top_k])
    picked = top[:, :z.top_k]
    if z.norm_topk:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    dense = (jax.nn.one_hot(chosen[:, :z.top_k], e, dtype=jnp.float32)
             * (picked * z.scaling)[..., None]).sum(1)
    return dense, gap


def layer_parts(x, w, z: Sizes):
    """One expert layer on x (T, C); ``w``: the leaves of its ``mlp``.
    -> (the held experts' part, the shared experts' part, the gap)."""
    f32 = lambda a: a.astype(jnp.float32)
    dense, gap = route(x, f32(w["gate.weight"]), z)
    held = w["experts.w_gate"].shape[0]
    here = jax.lax.dynamic_slice_in_dim(dense, z.offset, held, axis=1)

    def one(acc, e):
        wg, wu, wd, col = e
        return acc + col[:, None] * _swiglu(x, f32(wg), f32(wu), f32(wd)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        w["experts.w_gate"], w["experts.w_up"], w["experts.w_down"], here.T))
    shared = _swiglu(x, f32(w["shared_experts.gate_proj.weight"]),
                     f32(w["shared_experts.up_proj.weight"]),
                     f32(w["shared_experts.down_proj.weight"]))
    return routed, shared, gap


@functools.partial(jax.jit, static_argnames=("z", "moe"))
def _block(x, w, *, z: Sizes, moe: bool):
    """One decoder block on x (s, C); ``w`` as served (bf16). -> (x',
    the router's gap to a tie (s,): infinite in a dense block)."""
    f32 = lambda d: {k: v.astype(jnp.float32) for k, v in d.items()}
    ln1 = w["input_layernorm.weight"].astype(jnp.float32)
    ln2 = w["post_attention_layernorm.weight"].astype(jnp.float32)
    x = x + _attention(_rms(x, ln1, z.eps), f32(_group(w, "self_attn.")), z)
    h = _rms(x, ln2, z.eps)
    mlp = _group(w, "mlp.")
    if moe:
        routed, shared, gap = layer_parts(h, mlp, z)
        return x + routed + shared, gap
    d = f32(mlp)
    return (x + _swiglu(h, d["gate_proj.weight"], d["up_proj.weight"],
                        d["down_proj.weight"]),
            jnp.full(x.shape[:1], jnp.inf))


def hidden(state, ids, cfg):
    """ids (s,) -> ((s, C) float32 before the final norm; (s,)
    decidedness)."""
    z = sizes(cfg)
    x = jnp.take(state["model.embed_tokens.weight"], ids,
                 axis=0).astype(jnp.float32)
    decided = jnp.full(ids.shape, jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        x, gap = _block(x, _group(state, f"model.layers.{i}."), z=z,
                        moe=i >= cfg["first_k_dense_replace"])
        decided = jnp.minimum(decided, gap)
    return x, decided


def logits_at(state, ids, positions, cfg):
    """Reference logits (n, vocab held) at ``positions`` of one sequence
    ``ids`` (1, s). With ``reference_agreement`` in ``cfg``: non-finite
    where the request breaks that limit. The rows judged are the leading
    run of consecutive positions (the harness pads with position 0); row
    i's served token is ``ids[positions[i] + 1]``."""
    x, decided = hidden(state, ids[0], cfg)
    lg = _head(x[positions], state["model.norm.weight"],
               state["lm_head.weight"], eps=float(cfg["rms_norm_eps"]))
    rule = cfg.get("reference_agreement")
    if rule is None:
        return lg
    pos = np.asarray(positions)
    n = int((pos == pos[0] + np.arange(len(pos))).cumprod().sum())
    n = min(n, ids.shape[1] - 1 - int(pos[0]))
    served = np.asarray(ids[0])[pos[:n] + 1]
    lgn = np.asarray(lg[:n])
    got = agreement(lgn.max(-1) - lgn[np.arange(n), served],
                    np.asarray(decided)[pos[:n]], rule)
    log(phase="reference_deepseek_v2", first_position=int(pos[0]), **got)
    return lg if got["holds"] else jnp.full_like(lg, jnp.nan)
