"""Plain reference for arch ``minicpm_sala`` (MiniCPM-SALA: lightning
linear-attention layers and InfLLM-v2 block-sparse attention layers in
one model, MiniCPM's muP scalings): float32, full-precision matmuls, no
cache, no kernels, no chunk algebra, importing nothing of the program.

Model, hidden ``h``, ``a = scale_depth / sqrt(scale_depth_layers)`` (the
PUBLISHED depth, also in a cut)::

    h = scale_emb * E[ids]
    h = h + a * Mixer_l(RMSNorm(h));   h = h + a * FFN(RMSNorm(h))
    logits = W_head(RMSNorm(h) / (hidden_size / dim_model_base))

``FFN(x) = W_down(silu(W_gate x) * W_up x)``; ``mixer_types[l]`` says
which mixer layer ``l`` has. ``mup_denominator`` is not used.

``lightning-attn`` (H heads of d, no grouping): ``q, k, v = W_q x, W_k x,
W_v x``; RMSNorm over d on each head of q and k; rope (half-split pairs)
on q and k at the token's position; a head's state is a ``d x d`` matrix,
``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``S_{-1} = 0``, ``o_t = (q_t /
sqrt(d)) S_t``; ``y = W_o(RMSNorm_{H d}(o) * sigmoid(W_g x))``.
``lambda_h = exp(-2^(-8 (h + 1) / H))``, the same in every layer. The
recurrence is a ``lax.scan`` over tokens.

``minicpm4`` (InfLLM-v2; H query heads in G groups, one key and value
head a group, no rope): RMSNorm over d on each head of q and k, softmax
scale ``1 / sqrt(d)``, ``y = W_o(o * sigmoid(W_g x))``. The query at
position t sees n = t + 1 tokens. If ``n <= dense_len``: causal softmax
attention over all n. Otherwise, a KV head:

* compressed keys ``Kc_j = mean(k[stride j : stride j + kernel])`` for
  every j with ``stride j + kernel <= n``;
* per query head ``p^h = softmax_j(q^h . Kc_j / sqrt(d))``, per group
  ``r_j = sum_h p^h_j`` over its heads;
* per block b of ``block_size`` tokens ``R_b = max r_j`` over the j
  whose span ``[stride j, stride j + kernel)`` overlaps the block's;
* forced: the first ``init_blocks`` blocks and the ``window_size /
  block_size`` blocks that end at t's own; selected: the ``topk``
  blocks of largest ``R_b`` among the others (all of them if fewer);
* ``o^h`` is softmax attention of ``q^h`` over the tokens ``<= t`` of
  forced and selected blocks.

One selection a group a query TOKEN. **Departure**: the published
kernels approximate stage 1's softmax normaliser from coarser kernels;
here it is the exact one over the valid ``Kc_j``. Neighbouring blocks
share the compressed keys on their border, so two ``R_b`` are often
equal: of the blocks tied at the ``topk``-th place the lower indices are
taken (the published kernels' tie order is not known).

Weights are read by the run's ``state_dict`` names and upcast a layer at
a time; projections and the FFN run over blocks of token rows, attention
over blocks of query rows, so that 34,816 positions fit beside 10 GB of
weights. ``logits_at`` stops at the last position asked for (causal).
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
NEG = -1e30

Sizes = collections.namedtuple(
    "Sizes", "hidden heads groups d l_heads l_d eps theta scale_emb resid "
             "head_div kernel stride block topk window init dense")


def sizes(cfg: dict) -> Sizes:
    sp = cfg["sparse_config"]
    return Sizes(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        groups=cfg["num_key_value_heads"], d=cfg["head_dim"],
        l_heads=cfg["lightning_nh"], l_d=cfg["lightning_head_dim"],
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        scale_emb=float(cfg["scale_emb"]),
        resid=float(cfg["scale_depth"])
        / math.sqrt(cfg["scale_depth_layers"]),
        head_div=cfg["hidden_size"] / cfg["dim_model_base"],
        kernel=sp["kernel_size"], stride=sp["kernel_stride"],
        block=sp["block_size"], topk=sp["topk"], window=sp["window_size"],
        init=sp["init_blocks"], dense=sp["dense_len"])


def dims(cfg: dict) -> dict:
    """Sizes for ``opcount_sala``, from the published keys."""
    kinds = cfg["mixer_types"]
    sp = cfg["sparse_config"]
    return dict(
        h=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        sparse_layers=sum(k == "minicpm4" for k in kinds),
        lightning_layers=sum(k == "lightning-attn" for k in kinds),
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], l_heads=cfg["lightning_nh"],
        l_head_dim=cfg["lightning_head_dim"], ffn=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], tied=False, positions=0,
        kernel_size=sp["kernel_size"], kernel_stride=sp["kernel_stride"],
        block_size=sp["block_size"], topk=sp["topk"],
        window_size=sp["window_size"], init_blocks=sp["init_blocks"],
        dense_len=sp["dense_len"])


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rows(f, block: int, *xs):
    """``f(*xs)`` over blocks of ``block`` rows of each x (n, ...), n a
    multiple of ``block`` (or no more than it)."""
    n = xs[0].shape[0]
    if n <= block:
        return f(*xs)
    out = jax.lax.map(lambda a: f(*a), tuple(
        x.reshape(n // block, block, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape(n, *o.shape[2:]), out)


def _group(state: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


def _rope(x, theta: float):
    """x (n, H, d) at positions 0..n-1, half-split pairs (i, i + d/2)."""
    n, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def decays(heads: int):
    """``lambda_h``: Lightning Attention-2's slopes, one a head."""
    return np.exp(-2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)).astype(
        np.float32)


def lightning(x, w, z: Sizes, rows: int):
    """x (n, C) normed -> the mixer's output (n, C)."""
    n = x.shape[0]
    H, d = z.l_heads, z.l_d
    f32 = lambda name: w[name + ".weight"].astype(jnp.float32)
    proj = lambda xb: tuple(_mm(xb, f32(p)) for p in
                            ("q_proj", "k_proj", "v_proj", "o_gate"))
    q, k, v, g = _rows(proj, rows, x)
    q = _rope(_rms(q.reshape(n, H, d), f32("q_norm"), z.eps), z.theta)
    k = _rope(_rms(k.reshape(n, H, d), f32("k_norm"), z.eps), z.theta)
    v = v.reshape(n, H, d)
    lam = jnp.asarray(decays(H))[:, None, None]

    def token(S, qkv):
        qt, kt, vt = qkv
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hd,hde->he", qt / math.sqrt(d), S,
                             precision=_HI)

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    o = _rms(o.reshape(n, H * d), f32("o_norm"), z.eps)
    return _rows(lambda ob, gb: _mm(ob * jax.nn.sigmoid(gb), f32("o_proj")),
                 rows, o, g)


def compressed_keys(k, z: Sizes):
    """k (n, G, d) -> Kc (J, G, d), J = (n - kernel) // stride + 1 (0 if
    n < kernel): the mean over each window of ``kernel`` keys."""
    n = k.shape[0]
    J = max((n - z.kernel) // z.stride + 1, 0)
    idx = (np.arange(J)[:, None] * z.stride + np.arange(z.kernel)[None])
    return k[idx.reshape(-1)].reshape(J, z.kernel, *k.shape[1:]).mean(1)


def overlap(J: int, NB: int, z: Sizes):
    """(J, NB) bool: compressed key j's span meets block b's."""
    lo = np.arange(J)[:, None] * z.stride
    b0 = np.arange(NB)[None] * z.block
    return (lo < b0 + z.block) & (lo + z.kernel > b0)


def block_mask(q, kc, t, z: Sizes, NB: int):
    """The blocks each query reads: q (m, H, d) at positions t (m,), kc
    (J, G, d) -> (m, G, NB) bool. Rows with ``t + 1 <= dense_len`` read
    every block up to their own."""
    m, H, d = q.shape
    G = kc.shape[1]
    J = kc.shape[0]
    b = jnp.arange(NB)
    tb = t // z.block
    visible = b[None] <= tb[:, None]                            # (m, NB)
    forced = visible & ((b[None] < z.init)
                        | (b[None] > tb[:, None] - z.window // z.block))
    if J == 0:
        return jnp.broadcast_to(visible[:, None], (m, G, NB))
    s = jnp.einsum("mghd,jgd->mghj", q.reshape(m, G, H // G, d), kc,
                   precision=_HI) / math.sqrt(d)
    valid = (jnp.arange(J)[None] * z.stride + z.kernel
             <= t[:, None] + 1)[:, None, None, :]
    s = jnp.where(valid, s, NEG)
    p = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    r = p.sum(2)                                                # (m, G, J)
    ov = jnp.asarray(overlap(J, NB, z))                         # (J, NB)
    # R_b = max over the overlapping, valid j; a block a time keeps the
    # (m, G, J, NB) product out of memory
    R = jax.lax.map(
        lambda col: jnp.where(col[None, None] & valid[:, :, 0], r,
                              -jnp.inf).max(-1), ov.T,
        batch_size=16)                                          # (NB, m, G)
    R = jnp.moveaxis(R, 0, -1)
    cand = (visible & ~forced)[:, None, :]
    Rc = jnp.where(cand, R, -jnp.inf)
    # the topk largest; blocks that share a compressed key on their
    # border often tie exactly: the lower index goes first
    place = jnp.argsort(jnp.argsort(-Rc, axis=-1, stable=True), axis=-1)
    chosen = cand & (place < z.topk) & (Rc > -jnp.inf)
    sparse = forced[:, None] | chosen
    dense = (t + 1 <= z.dense)[:, None, None]
    return jnp.where(dense, visible[:, None], sparse)


def sparse(x, w, z: Sizes, rows: int, qrows: int, want_mask: bool = False):
    """x (n, C) normed -> the mixer's output (n, C) (or, for the tests,
    the (n, G, NB) blocks every query reads)."""
    n = x.shape[0]
    H, G, d = z.heads, z.groups, z.d
    NB = -(-n // z.block)
    f32 = lambda name: w[name + ".weight"].astype(jnp.float32)
    proj = lambda xb: tuple(_mm(xb, f32(p)) for p in
                            ("q_proj", "k_proj", "v_proj", "o_gate"))
    q, k, v, g = _rows(proj, rows, x)
    q = _rms(q.reshape(n, H, d), f32("q_norm"), z.eps)
    k = _rms(k.reshape(n, G, d), f32("k_norm"), z.eps)
    v = v.reshape(n, G, d)
    kc = compressed_keys(k, z)
    key_block = jnp.arange(n) // z.block

    def queries(qb, tb):                                # (m, H, d), (m,)
        blocks = block_mask(qb, kc, tb, z, NB)          # (m, G, NB)
        if want_mask:
            return blocks
        see = (jnp.take(blocks, key_block, axis=-1)
               & (jnp.arange(n)[None, None] <= tb[:, None, None]))
        s = jnp.einsum("mghd,ngd->mghn", qb.reshape(-1, G, H // G, d), k,
                       precision=_HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(see[:, :, None], s, NEG), -1)
        return jnp.einsum("mghn,ngd->mghd", p, v,
                          precision=_HI).reshape(-1, H * d)

    out = _rows(queries, qrows, q, jnp.arange(n))
    if want_mask:
        return out
    return _rows(lambda ob, gb: _mm(ob * jax.nn.sigmoid(gb), f32("o_proj")),
                 rows, out, g)


def _ffn(x, w, rows: int):
    f32 = lambda name: w[name + ".weight"].astype(jnp.float32)
    return _rows(lambda xb: _mm(jax.nn.silu(_mm(xb, f32("gate_proj")))
                                * _mm(xb, f32("up_proj")), f32("down_proj")),
                 rows, x)


@functools.partial(jax.jit, static_argnames=("z", "kind", "rows", "qrows"))
def _block(x, w, *, z: Sizes, kind: str, rows: int, qrows: int):
    """One decoder block on x (n, C); ``w`` as served."""
    ln = lambda name: w[name + ".weight"].astype(jnp.float32)
    xn = _rms(x, ln("input_layernorm"), z.eps)
    attn = _group(w, "self_attn.")
    y = (lightning(xn, attn, z, rows) if kind == "lightning-attn"
         else sparse(xn, attn, z, rows, qrows))
    x = x + z.resid * y
    xn = _rms(x, ln("post_attention_layernorm"), z.eps)
    return x + z.resid * _ffn(xn, _group(w, "mlp."), rows)


def _blocking(n: int, cfg: dict):
    """(n padded, token rows a block, query rows a block)."""
    rows = int(cfg.get("reference_rows", 2048))
    qrows = int(cfg.get("reference_query_rows", 128))
    if n <= qrows:
        return n, n, n
    n = -(-n // qrows) * qrows
    if n <= rows:
        return n, n, qrows
    return -(-n // rows) * rows, rows, qrows


def hidden(state, ids, cfg):
    """ids (n,) -> (n, C) float32 before the final norm."""
    z = sizes(cfg)
    n0 = ids.shape[0]
    n, rows, qrows = _blocking(n0, cfg)
    ids = jnp.pad(ids, (0, n - n0))         # causal: the tail changes nothing
    x = z.scale_emb * jnp.take(state["model.embed_tokens.weight"], ids,
                               axis=0).astype(jnp.float32)
    for i, kind in enumerate(cfg["mixer_types"]):
        x = _block(x, _group(state, f"model.layers.{i}."), z=z, kind=kind,
                   rows=rows, qrows=qrows)
    return x[:n0]


def selection(state, ids, cfg, layer: int):
    """For the tests: the (n, G, NB) blocks every query of sparse layer
    ``layer`` reads, given the model's own hidden states below it."""
    z = sizes(cfg)
    x = z.scale_emb * jnp.take(state["model.embed_tokens.weight"], ids,
                               axis=0).astype(jnp.float32)
    n = ids.shape[0]
    for i, kind in enumerate(cfg["mixer_types"][:layer]):
        x = _block(x, _group(state, f"model.layers.{i}."), z=z, kind=kind,
                   rows=n, qrows=n)
    w = _group(state, f"model.layers.{layer}.")
    xn = _rms(x, w["input_layernorm.weight"].astype(jnp.float32), z.eps)
    return sparse(xn, _group(w, "self_attn."), z, n, n, want_mask=True)


def logits_at(state, ids, positions, cfg):
    """Reference logits (len(positions), vocab) at ``positions`` of one
    sequence ``ids`` (1, s); the forward stops at the last of them."""
    z = sizes(cfg)
    last = int(np.asarray(positions).max())
    x = hidden(state, ids[0, :last + 1], cfg)
    xn = _rms(x[positions], state["model.norm.weight"].astype(jnp.float32),
              z.eps) / z.head_div
    return _mm(xn, state["lm_head.weight"].astype(jnp.float32))
