"""Mixture-of-experts: top-k gating + expert-parallel grouped experts.

Reference (SURVEY.md §2.6-EP): `MoELayer` with GShard top-2 / Switch top-1
gates (python/paddle/incubate/distributed/models/moe/{moe_layer.py,gate/}),
token dispatch via the `global_scatter`/`global_gather` NCCL all-to-all ops
(paddle/fluid/operators/collective/global_scatter_op.cu).

TPU-first design:
* experts live as ONE grouped weight per projection, shape
  (num_experts, d_in, d_out), expert dim sharded over the expert-parallel
  mesh axes — a single einsum runs all local experts on the MXU.
* dispatch/combine are GShard-style one-hot capacity tensors; constraining
  the dispatched activations to the expert sharding makes GSPMD emit the
  all_to_all the reference issues by hand.
* capacity is static (capacity_factor · k · tokens / E) so shapes stay
  XLA-friendly; overflow tokens are dropped exactly like the reference.
* the load-balancing aux loss is returned alongside the output; model code
  adds it to the task loss (the pipeline schedule threads it per-stage).
"""

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as init
from paddle_tpu.parallel.mp_layers import constrain

EP_AXES = ("dp",)   # default: expert parallelism rides the dp axis


def _ep_spec(ep_axes, ndim, extra=None):
    """Spec sharding dim0 (experts) over ep_axes (replicated when empty —
    the dropless path); `extra` maps dim→axis."""
    dims = [None] * ndim
    if ep_axes:
        dims[0] = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    for d, a in (extra or {}).items():
        dims[d] = a
    return P(*dims)


def _swiglu(xe, wg, wu, wd):
    """(E, C, h) grouped SwiGLU — the one expert-FFN math, shared by every
    dispatch path."""
    h1 = jnp.einsum("ech,ehf->ecf", xe, wg)
    h2 = jnp.einsum("ech,ehf->ecf", xe, wu)
    return jnp.einsum("ecf,efh->ech", F.silu(h1) * h2, wd)


def _slots(idx, pos, keep, cap, e):
    """Copy→slot map (t·k,): kept copies get unique slots in [0, e·cap);
    dropped copies get the OUT-OF-BOUNDS value e·cap (mode="drop" scatters
    discard them — never an in-bounds duplicate)."""
    return jnp.where(keep, idx * cap + pos, e * cap).reshape(-1)


def _token_copies(xt, k):
    """(t, h) → (t·k, h) row copies; the broadcast's VJP sums the k
    copy-grads back per token."""
    t, h = xt.shape
    return jnp.broadcast_to(xt[:, None], (t, k, h)).reshape(t * k, h)


def _slot_scatter(xt, idx, pos, keep, cap, e):
    """Tokens → flat (e·cap, h) expert buffer; dropped tokens get an OOB
    slot the scatter drops. Returns (buffer, slot ids)."""
    slot = _slots(idx, pos, keep, cap, e)
    xt_k = _token_copies(xt, idx.shape[1])
    buf = jnp.zeros((e * cap, xt.shape[-1]), xt.dtype).at[slot].set(
        xt_k, mode="drop", unique_indices=True)
    return buf, slot


def _slot_combine(ye_flat, slot, vals, keep, dtype):
    """Gather expert outputs back by slot and mix with gate weights."""
    t, k = vals.shape
    h = ye_flat.shape[-1]
    gathered = jnp.take(ye_flat, slot, axis=0, mode="fill",
                        fill_value=0).reshape(t, k, h)
    w = (vals * keep).astype(dtype)
    return jnp.einsum("tk,tkh->th", w, gathered)


def _perm_maps(slot, e, cap, tk):
    """Invert the copy→slot map: (buf_src (E·cap,) int, hit (E·cap,) bool)
    give, for every expert-buffer slot, which token-copy fills it (if any).

    One int32 scatter of tk scalars. Kept copies have unique in-bounds
    slots; dropped copies carry the OUT-OF-BOUNDS slot e*cap, which
    mode="drop" discards — so unique_indices holds. Cheap: the expensive
    ROW movement all happens as gathers — see _permute_rows."""
    buf_src = jnp.full((e * cap,), tk, jnp.int32).at[slot].set(
        jnp.arange(tk, dtype=jnp.int32), mode="drop", unique_indices=True)
    hit = buf_src < tk
    return jnp.where(hit, buf_src, 0), hit


@jax.custom_vjp
def _permute_rows(x, fwd_idx, fwd_ok, bwd_idx, bwd_ok):
    """out[i] = fwd_ok[i] ? x[fwd_idx[i]] : 0 — a (partial) row
    permutation whose backward is the INVERSE gather (bwd_idx/bwd_ok), so
    neither direction lowers to an XLA scatter (TPU scatters serialize
    row-by-row; gathers run at bandwidth). The index sets must be mutually
    inverse over their valid entries."""
    out = jnp.take(x, jnp.where(fwd_ok, fwd_idx, 0), axis=0)
    return jnp.where(fwd_ok[:, None], out, 0)


def _permute_rows_fwd(x, fwd_idx, fwd_ok, bwd_idx, bwd_ok):
    return _permute_rows(x, fwd_idx, fwd_ok, bwd_idx, bwd_ok), \
        (fwd_idx, fwd_ok, bwd_idx, bwd_ok)


def _permute_rows_bwd(res, g):
    fwd_idx, fwd_ok, bwd_idx, bwd_ok = res
    dx = jnp.take(g, jnp.where(bwd_ok, bwd_idx, 0), axis=0)
    dx = jnp.where(bwd_ok[:, None], dx, 0)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dx, f0(fwd_idx), f0(fwd_ok), f0(bwd_idx), f0(bwd_ok)


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _f0(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gather_dispatch(xt, buf_src, hit, slot_cl, keep, k):
    """out[s] = hit[s] ? xt[buf_src[s] // k] : 0 — dispatch straight from
    the (t, h) token rows into the flat (E·cap, h) per-expert blocks.

    Unlike `_forward_sort`'s two-step (materialize (t·k, h) row copies,
    then permute them), the token index is recovered from the copy index
    in the gather itself, so the expensive row movement is ONE gather per
    direction and the (t·k, h) intermediate never exists. Backward is the
    inverse gather (slot_cl/keep) followed by a contiguous segment-sum
    over each token's k copy rows — no row scatter anywhere."""
    out = jnp.take(xt, jnp.where(hit, buf_src // k, 0), axis=0)
    return jnp.where(hit[:, None], out, 0)


def _gather_dispatch_fwd(xt, buf_src, hit, slot_cl, keep, k):
    return _gather_dispatch(xt, buf_src, hit, slot_cl, keep, k), \
        (buf_src, hit, slot_cl, keep)


def _gather_dispatch_bwd(k, res, g):
    buf_src, hit, slot_cl, keep = res
    rows = jnp.take(g, jnp.where(keep, slot_cl, 0), axis=0)
    rows = jnp.where(keep[:, None], rows, 0)            # (t·k, h)
    t = keep.shape[0] // k
    dx = rows.reshape(t, k, -1).sum(axis=1)             # segment-sum
    return dx, _f0(buf_src), _f0(hit), _f0(slot_cl), _f0(keep)


_gather_dispatch.defvjp(_gather_dispatch_fwd, _gather_dispatch_bwd)


@jax.custom_vjp
def _combine_gather(ye, w, slot_cl, keep, buf_src, hit):
    """yt[t] = Σ_c w[t, c] · ye[slot(t, c)] — the combine as one
    inverse-permutation gather plus a per-token segment-sum over the k
    contiguous copy rows (the einsum below contracts exactly that).

    Backward re-disperses the incoming grad into the expert blocks with
    the FORWARD maps — d_ye[s] = w[token(s), choice(s)] · g[token(s)],
    again one gather — so neither direction lowers to an XLA row scatter
    (TPU row scatters serialize; gathers run near bandwidth)."""
    t, k = w.shape
    rows = jnp.take(ye, jnp.where(keep, slot_cl, 0), axis=0)
    rows = jnp.where(keep[:, None], rows, 0).reshape(t, k, -1)
    return jnp.einsum("tk,tkh->th", w, rows)


def _combine_gather_fwd(ye, w, slot_cl, keep, buf_src, hit):
    return _combine_gather(ye, w, slot_cl, keep, buf_src, hit), \
        (ye, w, slot_cl, keep, buf_src, hit)


def _combine_gather_bwd(res, g):
    ye, w, slot_cl, keep, buf_src, hit = res
    t, k = w.shape
    # d_ye: expert slot s receives its token's grad row scaled by its
    # gate weight — a gather over the forward copy→slot map
    src = jnp.where(hit, buf_src, 0)
    w_slot = jnp.where(hit, jnp.take(w.reshape(-1), src), 0)
    d_ye = (jnp.take(g, src // k, axis=0)
            * w_slot[:, None]).astype(ye.dtype)
    # d_w recomputes the gathered rows (cheap vs carrying (t·k, h))
    rows = jnp.take(ye, jnp.where(keep, slot_cl, 0), axis=0)
    rows = jnp.where(keep[:, None], rows, 0).reshape(t, k, -1)
    dw = jnp.einsum("th,tkh->tk", g, rows).astype(w.dtype)
    return d_ye, dw, _f0(slot_cl), _f0(keep), _f0(buf_src), _f0(hit)


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


def topk_routing(logits, k: int, capacity: int, normalize_topk: bool = True):
    """GShard-style top-k routing with static capacity — compact form.

    logits: (tokens, E) fp32. Returns (gate_idx (T, k) int, gate_vals
    (T, k) fp32, pos (T, k) int — the token's slot in its expert's queue,
    keep (T, k) bool, aux_loss scalar, stats dict). Choice 0 for all tokens
    claims capacity before choice 1 (reference GShardGate priority
    semantics). The compact form is O(T·k); the (T, E, C) one-hot tensors
    of `topk_gating` are derived views for callers that want them.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # (T, k)
    if normalize_topk:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balance aux (Switch/GShard): E * Σ_e mean_prob_e · frac_routed_e
    me = jnp.mean(probs, axis=0)                           # (E,)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                  axis=0)                                  # (E,)
    aux = e * jnp.sum(me * ce)

    # position in each expert's queue, choices processed in priority order:
    # flatten (k, T) so all choice-0 tokens precede choice-1 tokens
    mask = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)     # (T, k, E)
    mask_kt = jnp.swapaxes(mask, 0, 1).reshape(k * t, e)    # (k*T, E)
    pos_kt = jnp.cumsum(mask_kt, axis=0) - mask_kt          # claimed before me
    pos = jnp.swapaxes(pos_kt.reshape(k, t, e), 0, 1)       # (T, k, E)
    pos = jnp.sum(pos * mask, axis=-1)                      # (T, k)
    routed = gate_vals > 0.0
    keep = (pos < capacity) & routed                        # (T, k)

    load = jnp.sum(mask, axis=(0, 1)).astype(jnp.float32)   # (E,) tokens/exp
    n_routed = jnp.maximum(jnp.sum(routed.astype(jnp.float32)), 1.0)
    stats = {
        "moe_dropped_fraction":
            jnp.sum((routed & ~keep).astype(jnp.float32)) / n_routed,
        "moe_expert_load": load / jnp.maximum(jnp.sum(load), 1.0),
        "moe_capacity": jnp.asarray(float(capacity)),
        "moe_max_load_over_capacity": jnp.max(load) / float(capacity),
    }
    return gate_idx, gate_vals, pos, keep, aux, stats


def topk_gating(logits, k: int, capacity: int, normalize_topk: bool = True):
    """(T, E, C) one-hot view of `topk_routing` (legacy/einsum dispatch).

    Returns (combine (T, E, C), dispatch bool (T, E, C), aux_loss).
    """
    t, e = logits.shape
    gate_idx, gate_vals, pos, keep, aux, _ = topk_routing(
        logits, k, capacity, normalize_topk)
    mask = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)       # (T, k, E)
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)   # (T, k, C)
    contrib = (gate_vals * keep)[..., None] * pos_oh            # (T, k, C)
    combine = jnp.einsum("tkc,tke->tec", contrib, mask)
    dispatch = combine > 0.0
    return combine, dispatch, aux


def sigmoid_topk_routing(logits, correction_bias, k: int, *,
                         scaling: float = 1.0, normalize_topk: bool = True):
    """DeepSeek-V3's ``noaux_tc`` router for one expert group (no group
    limit): scores are ``sigmoid(logits)`` in float32; the ``k`` experts
    are chosen by ``score + correction_bias`` (the bias that balances
    load without an auxiliary loss steers the CHOICE only); the weights
    are the chosen experts' own scores, normalised to sum to 1 when
    ``normalize_topk`` and multiplied by ``scaling``. No capacity: no
    token is dropped. Returns ``(idx (T, k) int32, weights (T, k) f32)``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + correction_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling


def group_limited_topk_routing(logits, k: int, *, n_group: int,
                               topk_group: int, scaling: float = 1.0,
                               normalize_topk: bool = False):
    """DeepSeek-V2's ``group_limited_greedy`` router: scores are
    ``softmax(logits)`` over ALL experts in float32; the experts lie in
    ``n_group`` groups of consecutive ids and a group's score is its
    largest; the ``topk_group`` best groups are kept, every score outside
    them is set to 0, and the ``k`` largest of what is left are chosen.
    The weights are the chosen experts' own scores, normalised to sum to
    1 when ``normalize_topk``, and multiplied by ``scaling``. No
    capacity: no token is dropped. The groups bound how many chips of an
    expert-parallel layer a token visits. Returns ``(idx (T, k) int32,
    weights (T, k) f32)``."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    t, e = scores.shape
    best = scores.reshape(t, n_group, e // n_group).max(axis=-1)
    _, groups = jax.lax.top_k(best, topk_group)
    kept = jax.nn.one_hot(groups, n_group, dtype=jnp.bool_).any(axis=1)
    left = jnp.where(jnp.repeat(kept, e // n_group, axis=1), scores, 0.0)
    w, idx = jax.lax.top_k(left, k)
    if normalize_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling


class GShardGate(Layer):
    """Top-2 gate (reference: moe/gate/gshard_gate.py)."""

    top_k = 2

    def __init__(self, hidden_size, num_experts, capacity_factor=1.25):
        super().__init__()
        self.proj = _GateProj(hidden_size, num_experts)
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor

    def capacity(self, n_tokens):
        return max(4, int(math.ceil(
            self.capacity_factor * self.top_k * n_tokens / self.num_experts)))

    def forward(self, x_tokens):
        logits = self.proj(x_tokens)
        return topk_gating(logits, self.top_k,
                           self.capacity(x_tokens.shape[0]))

    def route(self, x_tokens):
        """Compact routing: (idx, vals, pos, keep, aux, stats, capacity)."""
        logits = self.proj(x_tokens)
        cap = self.capacity(x_tokens.shape[0])
        return topk_routing(logits, self.top_k, cap) + (cap,)


class SwitchGate(GShardGate):
    """Top-1 gate (reference: moe/gate/switch_gate.py)."""

    top_k = 1


class _GateProj(Layer):
    def __init__(self, hidden_size, num_experts):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden_size, num_experts),
            default_initializer=init.Normal(0.0, 0.02))

    def forward(self, x):
        # router math in fp32 (reference casts too — routing is precision-
        # sensitive)
        return jnp.matmul(x.astype(jnp.float32),
                          self.weight.astype(jnp.float32))


class GroupedSwiGLUExperts(Layer):
    """All experts' SwiGLU FFNs as three grouped (E, ·, ·) weights."""

    def __init__(self, num_experts, hidden_size, ffn_size, initializer_range=0.02,
                 ep_axes: Sequence[str] = EP_AXES, mp_axis: str = "mp",
                 dtype=None):
        super().__init__()
        w = init.Normal(0.0, initializer_range)
        e, h, f = num_experts, hidden_size, ffn_size
        self.w_gate = self.create_parameter((e, h, f), dtype=dtype,
                                            default_initializer=w)
        self.w_up = self.create_parameter((e, h, f), dtype=dtype,
                                          default_initializer=w)
        self.w_down = self.create_parameter((e, f, h), dtype=dtype,
                                            default_initializer=w)
        ep = tuple(ep_axes)
        self._parameters["w_gate"].pspec = _ep_spec(ep, 3, {2: mp_axis})
        self._parameters["w_up"].pspec = _ep_spec(ep, 3, {2: mp_axis})
        self._parameters["w_down"].pspec = _ep_spec(ep, 3, {1: mp_axis})
        self.ep_axes = ep
        self.mp_axis = mp_axis

    def forward(self, xe):
        """xe: (E, C_total, h) dispatched tokens → (E, C_total, h)."""
        spec = lambda nd: _ep_spec(self.ep_axes, nd)
        for a in self.ep_axes:
            xe = constrain(xe, spec, a)     # all_to_all into expert shards
        y = _swiglu(xe, self.w_gate, self.w_up, self.w_down)
        for a in self.ep_axes:
            y = constrain(y, spec, a)
        return y

    def forward_ragged(self, xs, group_sizes):
        """Dropless path: xs (N, h) tokens sorted by expert, group_sizes
        (E,) int32 — per-expert contiguous segment lengths. Ragged grouped
        matmuls (jax.lax.ragged_dot) instead of capacity padding; no token
        is ever dropped. Experts must be replicated across devices here
        (the capacity path is the EP-sharded one)."""
        dt = xs.dtype
        h1 = jax.lax.ragged_dot(xs, self.w_gate.astype(dt), group_sizes)
        h2 = jax.lax.ragged_dot(xs, self.w_up.astype(dt), group_sizes)
        return jax.lax.ragged_dot(F.silu(h1) * h2, self.w_down.astype(dt),
                                  group_sizes)


class MoELayer(Layer):
    """Token-choice MoE block: gate → all_to_all dispatch → grouped experts
    → combine. Returns (output, aux_loss).

    Reference parity: paddle.incubate.distributed.models.moe.MoELayer
    (gate=GShard top-2 or Switch top-1, capacity dropping, aux loss).
    """

    def __init__(self, hidden_size, ffn_size, num_experts, top_k=None,
                 capacity_factor=1.25, gate: str = "gshard",
                 initializer_range=0.02, ep_axes: Sequence[str] = EP_AXES,
                 mp_axis: str = "mp", dtype=None, dropless: bool = False,
                 dispatch_mode: str = "scatter"):
        super().__init__()
        gate_cls = {"gshard": GShardGate, "switch": SwitchGate}[gate]
        if gate == "switch" and top_k not in (None, 1):
            raise ValueError(f"gate='switch' is top-1 routing; got top_k={top_k}")
        if dispatch_mode not in ("scatter", "sort", "fused", "einsum",
                                 "alltoall"):
            raise ValueError(f"unknown dispatch_mode {dispatch_mode!r}")
        self.gate = gate_cls(hidden_size, num_experts,
                             capacity_factor=capacity_factor)
        if top_k is not None:
            self.gate.top_k = top_k
        # dropless replicates experts (ragged segments don't EP-shard)
        self.experts = GroupedSwiGLUExperts(
            num_experts, hidden_size, ffn_size,
            initializer_range=initializer_range,
            ep_axes=() if dropless else ep_axes,
            mp_axis=mp_axis, dtype=dtype)
        self.num_experts = num_experts
        self.hidden_size = hidden_size
        self.dropless = dropless
        self.dispatch_mode = dispatch_mode

    def _forward_capacity(self, xt, dtype):
        """Scatter dispatch: O(T·k) index ops instead of the O(T·E·C)
        one-hot einsums (the global_scatter/gather mechanism cost parity —
        SURVEY.md §2.6-EP)."""
        e = self.num_experts
        idx, vals, pos, keep, aux, stats, cap = self.gate.route(xt)
        buf, slot = _slot_scatter(xt.astype(dtype), idx, pos, keep, cap, e)
        ye = self.experts(buf.reshape(e, cap, -1)).reshape(e * cap, -1)
        yt = _slot_combine(ye, slot, vals, keep, dtype)
        return yt, aux, stats

    def _forward_sort(self, xt, dtype):
        """Permutation dispatch: one cheap int32 SCALAR scatter builds the
        inverse copy→slot map (_perm_maps), then dispatch and combine run
        as row gathers in forward AND backward (custom-VJP
        inverse-permutation) — no ROW scatter anywhere. TPU row-scatters
        serialize; gathers run near bandwidth. Kept as the A/B baseline
        for 'fused', which removes this path's (t·k, h) copy
        materialization and one permutation pass per direction."""
        e = self.num_experts
        t, h = xt.shape
        idx, vals, pos, keep, aux, stats, cap = self.gate.route(xt)
        k = idx.shape[1]
        keep_f = keep.reshape(-1)
        slot = _slots(idx, pos, keep, cap, e)
        slot_cl = jnp.clip(slot, 0, e * cap - 1)
        buf_src, hit = _perm_maps(slot, e, cap, t * k)
        xt_k = _token_copies(xt.astype(dtype), k)
        buf = _permute_rows(xt_k, buf_src, hit, slot_cl, keep_f)
        ye = self.experts(buf.reshape(e, cap, h)).reshape(e * cap, h)
        gathered = _permute_rows(ye, slot_cl, keep_f, buf_src, hit)
        w = (vals * keep).astype(dtype)
        yt = jnp.einsum("tk,tkh->th", w, gathered.reshape(t, k, h))
        return yt, aux, stats

    def _forward_fused(self, xt, dtype):
        """Fused permutation dispatch — the r5 dispatch-residual redesign.

        'sort' runs FOUR row passes per direction (materialize the
        (t·k, h) token copies, permute them into the expert buffer;
        permute the outputs back, weighted-sum them). Here the dispatch
        permutation is fused with the expert matmul input staging: the
        (E, cap, h) blocks are gathered DIRECTLY from the (t, h) token
        rows (token index recovered from the inverse copy→slot map inside
        the gather), and the combine is one inverse gather + per-token
        segment-sum with the gate weights. Two row passes per direction,
        no (t·k, h) intermediate, still zero row scatters (custom VJPs
        mirror each gather with its inverse)."""
        e = self.num_experts
        t, h = xt.shape
        idx, vals, pos, keep, aux, stats, cap = self.gate.route(xt)
        k = idx.shape[1]
        keep_f = keep.reshape(-1)
        slot = _slots(idx, pos, keep, cap, e)
        slot_cl = jnp.clip(slot, 0, e * cap - 1)
        buf_src, hit = _perm_maps(slot, e, cap, t * k)
        buf = _gather_dispatch(xt.astype(dtype), buf_src, hit, slot_cl,
                               keep_f, k)
        ye = self.experts(buf.reshape(e, cap, h)).reshape(e * cap, h)
        w = (vals * keep).astype(dtype)
        yt = _combine_gather(ye, w, slot_cl, keep_f, buf_src, hit)
        return yt, aux, stats

    def _forward_einsum(self, xt, dtype):
        """Legacy (T, E, C) one-hot dispatch — kept for A/B comparison."""
        combine, dispatch, aux = self.gate(xt)            # (T, E, C)
        xe = jnp.einsum("tec,th->ech", dispatch.astype(dtype), xt)
        ye = self.experts(xe)                             # (E, C, h)
        yt = jnp.einsum("tec,ech->th", combine.astype(dtype), ye)
        return yt, aux, None

    def _forward_alltoall(self, xt, dtype):
        """Explicit lax.all_to_all dispatch over the EP axis inside a
        shard_map — the literal global_scatter/global_gather mechanism
        (SURVEY.md §2.6-EP, collective/global_scatter_op.cu): each device
        routes its token shard, exchanges fixed-capacity per-destination
        buffers with an all_to_all, runs its local experts, and reverses
        the exchange to combine.

        Requires an active hybrid mesh whose `ep_axes` product divides
        num_experts; tokens must be shardable over that axis. Composes
        with mp_degree > 1: each expert's FFN is column/row-sharded over
        the mp axis inside the same shard_map (psum on the down-proj).

        CPU-sim caveat: XLA:CPU runs one thread per simulated device with
        a 40 s collective-rendezvous timeout; on a single-core host, long
        uninterrupted loops over this program can starve a participant and
        abort (rendezvous.cc "Termination timeout"). Real multi-chip
        executions are unaffected."""
        from jax import shard_map

        from paddle_tpu.parallel.topology import (
            get_hybrid_communicate_group)

        hcg = get_hybrid_communicate_group()
        if hcg is None:
            raise RuntimeError(
                "dispatch_mode='alltoall' needs fleet.init (an active "
                "hybrid mesh); use 'scatter' for single-mesh-free runs")
        mesh = hcg.mesh
        ep = self.experts.ep_axes
        if not ep:
            raise ValueError(
                "dispatch_mode='alltoall' needs ep_axes (experts replicated "
                "with ep_axes=() have no axis to exchange over — use "
                "'scatter' or 'sort')")
        # multiple EP axes act as ONE flattened axis (row-major over the
        # tuple — the same convention shard_map uses for a dim sharded
        # over an axis tuple, so the exchange and the sharding agree)
        axis = ep if len(ep) > 1 else ep[0]
        mp_axis = self.experts.mp_axis
        mp_deg = mesh.shape.get(mp_axis, 1)
        pdim = 1
        for a in ep:
            pdim *= mesh.shape[a]
        e = self.num_experts
        if e % pdim or xt.shape[0] % pdim:
            raise ValueError(
                f"the EP axes {ep} (size {pdim}) must divide both "
                f"num_experts {e} and the token count {xt.shape[0]}")
        e_loc = e // pdim
        gate_w = self.gate.proj.weight
        wg, wu, wd = (self.experts.w_gate, self.experts.w_up,
                      self.experts.w_down)
        cap = self.gate.capacity(xt.shape[0] // pdim)
        top_k = self.gate.top_k

        def body(xt_loc, gate_w, wg, wu, wd):
            # xt_loc (T_loc, h); expert weights sharded dim0 over the EP
            # axis and (when mp_deg > 1) the ffn dim over the mp axis —
            # each device holds a column slice of its local experts' FFNs
            # and the down-proj partial sums reduce over mp (Megatron-style
            # TP inside each expert, composed with EP alltoall)
            h = xt_loc.shape[-1]
            logits = jnp.matmul(xt_loc.astype(jnp.float32),
                                gate_w.astype(jnp.float32))
            idx, vals, pos, keep, aux, _ = topk_routing(logits, top_k, cap)
            # slot layout groups experts by owner: dest p owns experts
            # [p*e_loc, (p+1)*e_loc)
            send, slot = _slot_scatter(xt_loc, idx, pos, keep, cap, e)
            send = send.reshape(pdim, e_loc * cap, h)
            # exchange: device q's block p  →  device p's block q
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # recv (pdim_src, e_loc*cap, h) → (e_loc, pdim_src*cap, h)
            xe = recv.reshape(pdim, e_loc, cap, h).transpose(1, 0, 2, 3) \
                .reshape(e_loc, pdim * cap, h)
            ye = _swiglu(xe, wg, wu, wd)
            if mp_deg > 1:      # reduce the ffn-sharded contraction
                ye = jax.lax.psum(ye, mp_axis)
            # reverse exchange
            back = ye.reshape(e_loc, pdim, cap, h).transpose(1, 0, 2, 3) \
                .reshape(pdim, e_loc * cap, h)
            got = jax.lax.all_to_all(back, axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            yt = _slot_combine(got.reshape(e * cap, h), slot, vals, keep,
                               xt_loc.dtype)
            # aux is a per-shard mean over local tokens; average over shards
            return yt, jax.lax.pmean(aux, axis)

        mp_s = mp_axis if mp_deg > 1 else None
        yt, aux = shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(),
                      P(axis, None, mp_s),     # w_gate (E, h, f)
                      P(axis, None, mp_s),     # w_up
                      P(axis, mp_s, None)),    # w_down (E, f, h)
            out_specs=(P(axis), P()),
            check_vma=False)(xt, gate_w, wg, wu, wd)
        return yt.astype(dtype), aux, None

    def _forward_dropless(self, xt, dtype):
        """Sort + ragged grouped matmul: every routed token is computed
        (MegaBlocks-style dropless)."""
        e = self.num_experts
        idx, vals, pos, keep, aux, stats, _ = self.gate.route(xt)
        t, k = idx.shape
        h = xt.shape[-1]
        e_flat = idx.reshape(-1)                          # (T·k,)
        order = jnp.argsort(e_flat, stable=True)
        xt_k = jnp.broadcast_to(xt[:, None], (t, k, h)).reshape(t * k, h)
        xs = jnp.take(xt_k, order, axis=0)
        group_sizes = jnp.bincount(e_flat, length=e).astype(jnp.int32)
        ys = self.experts.forward_ragged(xs, group_sizes)
        inv = jnp.argsort(order, stable=True)
        ys = jnp.take(ys, inv, axis=0).reshape(t, k, h)
        w = vals.astype(dtype)                            # no capacity drop
        yt = jnp.einsum("tk,tkh->th", w, ys)
        stats = dict(stats)
        stats["moe_dropped_fraction"] = jnp.zeros(())
        return yt, aux, stats

    def forward(self, x, return_stats: bool = False):
        b, s, h = x.shape
        xt = x.reshape(b * s, h)
        if self.dropless:
            yt, aux, stats = self._forward_dropless(xt, x.dtype)
        elif self.dispatch_mode == "scatter":
            yt, aux, stats = self._forward_capacity(xt, x.dtype)
        elif self.dispatch_mode == "sort":
            yt, aux, stats = self._forward_sort(xt, x.dtype)
        elif self.dispatch_mode == "fused":
            yt, aux, stats = self._forward_fused(xt, x.dtype)
        elif self.dispatch_mode == "alltoall":
            yt, aux, stats = self._forward_alltoall(xt, x.dtype)
        else:
            yt, aux, stats = self._forward_einsum(xt, x.dtype)
        out = yt.reshape(b, s, h)
        if return_stats:
            return out, aux, stats
        return out, aux
