"""Plain reference for arch ``xing4`` (Xing4.0-29B-A4B): float32, full
precision matmuls, no cache, no kernels, written from the equations the
published keys name and importing nothing of the program.

Per token the state is ``X`` (n streams x C). Around each sub-layer F
(attention, then FFN or experts), with its own ``phi``, gains and biases
(manifold-constrained hyper-connections, arXiv:2512.24880)::

    x~ = vec(X) / rms(vec(X));  m = x~ phi  -> m_pre (n), m_post (n), M_res (n x n)
    H_pre = sigmoid(a_pre m_pre + b_pre);  H_post = 2 sigmoid(a_post m_post + b_post)
    H_res = Sinkhorn(clip(a_res M_res + b_res, lo, hi))     exp, 20 x (columns, rows)
    X' = H_res X + H_post^T (x) F(RMSNorm(H_pre X))

Attention is DeepSeek-V2's MLA in its expanded form: ``c_q =
RMSNorm(x W_qa)``, ``[q_n | q_r] = c_q W_qb`` a head, ``[c_kv | k_r] = x
W_kva``, ``c_kv = RMSNorm(c_kv)``, rope at YaRN frequencies on ``q_r``
and the one shared ``k_r`` (half-split pairs), ``[k_n | v] = c_kv W_kvb``
a head, scores ``(q_n k_n + q_r k_r) (d_n + d_r)^-1/2 m^2`` with ``m =
0.1 mscale_all_dim ln(factor) + 1``, causal softmax, ``W_o``. The FFN
is SwiGLU in the leading dense layers; elsewhere DeepSeek-V3's
``noaux_tc`` router (``s = sigmoid(x W_g)``, top-k of ``s + bias``,
weights ``s / (sum s + 1e-20) * routed_scaling_factor``) over all
experts plus one shared expert; no token is dropped. Read-in copies the
embedding to every stream, read-out sums them; a final RMSNorm and an
untied head follow. The MTP layer: ``W_p [RMSNorm(h_t) ;
RMSNorm(Emb(token_t+1))]``, one expert block, its own final norm, the
shared head.

Weights are read by the run's ``state_dict`` names and upcast as they
are used: a layer at a time, the routed experts one at a time (a scan
over the expert axis), so the reference fits beside the served weights.
Attention scores are taken in blocks of query rows.

**What ``correct`` holds a served request to.** A top-k router is not
continuous: where the k-th and the (k+1)-th score lie closer than the
served precision resolves, the served model may take the other expert,
and from there on that token's state is another one (a flipped expert
moves a layer's output by half; later layers flip with it). Against
131072 random-weight logits that reads as a margin of 1 to 5 at the
served token, in bf16 as stated and in any lower precision alike, so
the worst margin of a run (the one limit ``serve.check_outputs`` has)
cannot tell them apart. The reference knows, though, how far from a tie
its OWN routing stood at every token: ``decidedness`` is the least gap
between the k-th and the (k+1)-th biased score over the expert layers.
``logits_at`` therefore applies a second limit, the configuration's
``reference_agreement``: of the ``decided_share`` of a request's rows
that are furthest from a tie, no more than ``max_share`` (and never
fewer than ``min_rows`` allowed) may have the served token more than
``margin`` under the reference's maximum. In bf16 as stated a row that
far from a tie keeps its experts and its margin is logit rounding
(0.8 % of such rows over the margin on the v5e); with int8 weights it
flips fifteen to thirty times as often (13 to 23 %). A
request that breaks the limit gets non-finite logits back, which
``check_outputs`` reports as not correct; every call says what it
counted on a ``bench:`` line. (``check_outputs`` cannot be handed the
program's expert choices nor a second limit without an edit to
``serve.py``: PERF.md section 7.)
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import log

_HI = jax.lax.Precision.HIGHEST
_Q_ROWS = 256

Sizes = collections.namedtuple(
    "Sizes", "heads d_n d_r d_v d_c eps n iters hc_eps lo hi top_k scaling "
             "norm_topk theta factor orig_max beta_fast beta_slow mscale "
             "mscale_all")


def sizes(cfg: dict) -> Sizes:
    rs = cfg["rope_scaling"]
    return Sizes(
        heads=cfg["num_attention_heads"], d_n=cfg["qk_nope_head_dim"],
        d_r=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_c=cfg["kv_lora_rank"], eps=float(cfg["rms_norm_eps"]),
        n=cfg["hc_mult"], iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]), lo=float(cfg["mhc_h_res_clamp_min"]),
        hi=float(cfg["mhc_h_res_clamp_max"]),
        top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        theta=float(cfg["rope_theta"]), factor=float(rs["factor"]),
        orig_max=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs.get("mscale", 1)),
        mscale_all=float(rs.get("mscale_all_dim", 0)))


def dims(cfg: dict) -> dict:
    """Sizes for ``opcount_xing4``, from the published keys."""
    dense = cfg["first_k_dense_replace"]
    return dict(
        h=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        dense_layers=dense, moe_layers=cfg["num_hidden_layers"] - dense,
        heads=cfg["num_attention_heads"], d_n=cfg["qk_nope_head_dim"],
        d_r=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        d_c=cfg["kv_lora_rank"], d_q=cfg["q_lora_rank"],
        cache_lanes=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
        ffn=cfg["intermediate_size"], expert_ffn=cfg["moe_intermediate_size"],
        experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"], streams=cfg["hc_mult"],
        vocab=cfg["vocab_size"], tied=False, positions=0)


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rms(x, w, eps):
    y = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return y if w is None else y * w


def _yarn_scale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(z: Sizes) -> np.ndarray:
    """d_r / 2 inverse frequencies: extrapolated (plain rope) where a
    dimension turns more than beta_fast times in the original context,
    interpolated (divided by factor) where fewer than beta_slow, a
    linear ramp between."""
    d = z.d_r
    plain = z.theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)

    def turns_to_dim(turns):
        return d * math.log(z.orig_max / (turns * 2 * math.pi)) / (
            2 * math.log(z.theta))

    low = max(math.floor(turns_to_dim(z.beta_fast)), 0)
    high = min(math.ceil(turns_to_dim(z.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / z.factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rope(x, z: Sizes):
    """x (s, heads, d_r): pairs (i, i + d_r/2) turned by pos * freq_i."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(z))[None]
    ratio = _yarn_scale(z.factor, z.mscale) / _yarn_scale(z.factor,
                                                          z.mscale_all)
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * ratio)[:, None]
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * ratio)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)     # columns
        m = m / (m.sum(-1, keepdims=True) + eps)     # rows
    return m


def _mixers(X, w, z: Sizes):
    """X (s, n, C) -> H_pre (s, n), H_post (s, n), H_res (s, n, n)."""
    s, n, c = X.shape
    m = _mm(_rms(X.reshape(s, n * c), None, z.eps), w["phi"])
    pre = jax.nn.sigmoid(w["alpha_pre"] * m[:, :n] + w["pre_bias"])
    post = 2.0 * jax.nn.sigmoid(w["alpha_post"] * m[:, n:2 * n]
                                + w["post_bias"])
    res = w["alpha_res"] * m[:, 2 * n:].reshape(s, n, n) + w["res_bias"]
    return pre, post, sinkhorn(jnp.clip(res, z.lo, z.hi), z.iters, z.hc_eps)


def _around(X, w, z, sub_layer):
    """X' = H_res X + H_post^T (x) sub_layer(H_pre X)."""
    pre, post, res = _mixers(X, w, z)
    y = sub_layer(jnp.einsum("sn,snc->sc", pre, X, precision=_HI))
    return (jnp.einsum("sij,sjc->sic", res, X, precision=_HI)
            + post[:, :, None] * y[:, None, :])


def _attention(x, w, z: Sizes):
    s = x.shape[0]
    H = z.heads
    c_q = _rms(_mm(x, w["q_a_proj.weight"]), w["q_a_layernorm.weight"], z.eps)
    q = _mm(c_q, w["q_b_proj.weight"]).reshape(s, H, z.d_n + z.d_r)
    kva = _mm(x, w["kv_a_proj_with_mqa.weight"])
    c_kv = _rms(kva[:, :z.d_c], w["kv_a_layernorm.weight"], z.eps)
    k_r = _rope(kva[:, None, z.d_c:], z)[:, 0]                  # (s, d_r)
    q_n, q_r = q[..., :z.d_n], _rope(q[..., z.d_n:], z)
    kv = _mm(c_kv, w["kv_b_proj.weight"]).reshape(s, H, z.d_n + z.d_v)
    k_n, v = kv[..., :z.d_n], kv[..., z.d_n:]
    m = _yarn_scale(z.factor, z.mscale_all)
    scale = (z.d_n + z.d_r) ** -0.5 * m * m
    kpos = jnp.arange(s)

    def rows(args):
        qn, qr, qpos = args
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision=_HI)
              + jnp.einsum("qhd,kd->hqk", qr, k_r, precision=_HI)) * scale
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=_HI)

    if s > _Q_ROWS and s % _Q_ROWS == 0:
        nq = s // _Q_ROWS
        att = jax.lax.map(rows, (q_n.reshape(nq, _Q_ROWS, H, z.d_n),
                                 q_r.reshape(nq, _Q_ROWS, H, z.d_r),
                                 kpos.reshape(nq, _Q_ROWS)))
    else:
        att = rows((q_n, q_r, kpos))
    return _mm(att.reshape(s, H * z.d_v), w["o_proj.weight"])


def _swiglu(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def _experts(x, w, z: Sizes):
    """w: bf16 (or float32) leaves of one expert layer's ``mlp``."""
    f32 = lambda a: a.astype(jnp.float32)
    s = jax.nn.sigmoid(_mm(x, f32(w["gate.weight"])))           # (T, E)
    best, chosen = jax.lax.top_k(
        s + f32(w["gate.e_score_correction_bias"]),
        min(z.top_k + 1, s.shape[-1]))
    # how far the choice stood from a tie: k-th less (k+1)-th biased score
    gap = (best[:, z.top_k - 1] - best[:, z.top_k]
           if best.shape[-1] > z.top_k else jnp.full(s.shape[:1], jnp.inf))
    chosen = chosen[:, :z.top_k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if z.norm_topk:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * z.scaling
    dense = (jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32)
             * picked[..., None]).sum(1)                        # (T, E)

    def one(acc, e):
        wg, wu, wd, col = e
        return acc + col[:, None] * _swiglu(x, f32(wg), f32(wu), f32(wd)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        w["experts.w_gate"], w["experts.w_up"], w["experts.w_down"],
        dense.T))
    return y + _swiglu(x, f32(w["shared_experts.gate_proj.weight"]),
                       f32(w["shared_experts.up_proj.weight"]),
                       f32(w["shared_experts.down_proj.weight"])), gap


def _group(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("z", "moe"))
def _block(X, w, *, z: Sizes, moe: bool):
    """One decoder block on X (s, n, C); ``w`` as served (bf16). ->
    (X', the router's gap to a tie (s,): infinite in a dense block)."""
    f32 = lambda d: {k: v.astype(jnp.float32) for k, v in d.items()}
    attn = f32(_group(w, "self_attn."))
    ln1 = w["input_layernorm.weight"].astype(jnp.float32)
    ln2 = w["post_attention_layernorm.weight"].astype(jnp.float32)
    X = _around(X, f32(_group(w, "attn_hc.")), z,
                lambda h: _attention(_rms(h, ln1, z.eps), attn, z))
    mlp = _group(w, "mlp.")
    gaps = [jnp.full(X.shape[:1], jnp.inf)]
    if moe:
        def ffn(h):
            y, gap = _experts(_rms(h, ln2, z.eps), mlp, z)
            gaps.append(gap)
            return y
    else:
        d = f32(mlp)
        ffn = lambda h: _swiglu(_rms(h, ln2, z.eps), d["gate_proj.weight"],
                                d["up_proj.weight"], d["down_proj.weight"])
    X = _around(X, f32(_group(w, "ffn_hc.")), z, ffn)    # fills ``gaps``
    return X, gaps[-1]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    return _mm(_rms(x, norm_w.astype(jnp.float32), eps),
               head_w.astype(jnp.float32))


def _read_in(x, n):
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))


def hidden(state, ids, cfg):
    """ids (s,) -> ((s, C) float32: the streams' sum before the final
    norm; (s,) decidedness: the least gap to a routing tie over the
    expert layers)."""
    z = sizes(cfg)
    X = _read_in(jnp.take(state["model.embed_tokens.weight"], ids,
                          axis=0).astype(jnp.float32), z.n)
    decided = jnp.full(ids.shape, jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        X, gap = _block(X, _group(state, f"model.layers.{i}."), z=z,
                        moe=i >= cfg["first_k_dense_replace"])
        decided = jnp.minimum(decided, gap)
    return X.sum(axis=1), decided


def agreement(margins, decided, rule: dict) -> dict:
    """The second limit (module docstring): ``margins`` (n,) reference
    maximum less the served token's logit, ``decided`` (n,) the rows'
    decidedness. -> what was counted, and ``holds``. ``ladder`` gives,
    for other shares of the rows than the rule's, [rows, rows over half
    the margin, over the margin, over twice the margin]."""
    n = len(margins)
    order = np.argsort(-decided, kind="stable")

    def most_decided(share):
        return order[:max(1, int(n * share))]

    top = most_decided(rule["decided_share"])
    over = int((margins[top] > rule["margin"]).sum())
    allowed = max(int(rule["min_rows"]), int(rule["max_share"] * len(top)))
    ladder = {str(share): [len(rows)] + [
        int((margins[rows] > rule["margin"] * k).sum()) for k in (0.5, 1, 2)]
        for share in (0.125, 0.25, 0.5, 1.0)
        for rows in [most_decided(share)]}
    return dict(rows=n, decided_rows=len(top),
                least_decided_gap=float(decided[top].min()), over=over,
                allowed=allowed, worst_margin=float(margins.max()),
                worst_margin_decided=float(margins[top].max()),
                ladder=ladder, holds=over <= allowed)


def logits_at(state, ids, positions, cfg):
    """Reference logits (n, vocab) at ``positions`` of one sequence
    ``ids`` (1, s). With ``reference_agreement`` in ``cfg``: non-finite
    where the request breaks that limit (module docstring). The rows
    judged are the leading run of consecutive positions (the harness
    pads with position 0); row i's served token is ``ids[positions[i] +
    1]``."""
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
    log(phase="reference_xing4", first_position=int(pos[0]), **got)
    return lg if got["holds"] else jnp.full_like(lg, jnp.nan)


def mtp_logits(state, ids, cfg):
    """The MTP layer's logits (s - 1, vocab) for one sequence ``ids``
    (1, s): row t predicts token t + 2."""
    z = sizes(cfg)
    eps = float(cfg["rms_norm_eps"])
    f32 = lambda name: state[name].astype(jnp.float32)
    h = hidden(state, ids[0], cfg)[0][:-1]
    emb = jnp.take(state["model.embed_tokens.weight"], ids[0, 1:],
                   axis=0).astype(jnp.float32)
    both = jnp.concatenate([_rms(h, f32("model.mtp.hnorm.weight"), eps),
                            _rms(emb, f32("model.mtp.enorm.weight"), eps)], -1)
    X = _read_in(_mm(both, f32("model.mtp.eh_proj.weight")), z.n)
    X, _ = _block(X, _group(state, "model.mtp.block."), z=z, moe=True)
    return _head(X.sum(axis=1), state["model.mtp.norm.weight"],
                 state["lm_head.weight"], eps=eps)
