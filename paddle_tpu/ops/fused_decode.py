"""Fused decode-step kernel — the fused_multi_transformer analog.

Reference: paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu +
masked_multihead_attention (SURVEY.md §2.2 fusion row, §2.8-1, §7 stage 6):
the reference's inference crown jewel runs one token through the whole
decoder stack with hand-fused CUDA kernels (qkv + rope + KV-cache append +
masked attention + FFN), streaming each layer's weights exactly once.

TPU-native design: ONE `pallas_call` for the entire stack per decode step.

* grid = (num_layers, 1 + ffn_blocks): phase 0 of each layer does
  rmsnorm→qkv→rope→cache-append→masked attention over the *filled prefix
  only*→o-proj; phases 1..J stream the SwiGLU FFN in column blocks.
* Layer weights ride BlockSpecs indexed by the layer grid dim, so Mosaic's
  pipeline double-buffers them: layer l+1's weights stream from HBM while
  layer l computes — the "stream weights once, overlap with compute"
  property the CUDA kernel gets from its warp pipeline.
* The KV cache lives in HBM (`pl.ANY` memory space, input/output aliased —
  updated in place). The new token's k/v is DMA'd into slot `pos`; the
  attention loop then DMAs 128-token chunks of the *filled* prefix
  [0, pos] into VMEM — unlike the XLA scan path it never touches the
  unfilled tail, and the whole residual stream stays in fp32 in VMEM.
* The hidden state x crosses grid steps in a VMEM scratch accumulator, so
  the only HBM traffic per step is weights (once), the filled KV prefix,
  and one token's cache append — which IS the decode roofline.

The stack covers the Llama block (RMSNorm / GQA / RoPE / SwiGLU, no
biases) and, via `arch="gpt"`, the GPT block (LayerNorm+bias / MHA / no
rope / GELU) — the architecture the reference's fused_multi_transformer
itself serves. `fused_decode_reference` is the jnp twin used for numerics
tests and as the non-TPU fallback; `examples/decode_bench.py` measures
the win.

The serving engine's PAGED variants (`fused_paged_decode_step`, further
down) keep the cache as a pool of blocks behind a block table. There a
chunk of the walk is one block, and the ONE paged kernel walks a flat
list of (row, block) pairs, each row's own blocks (`paged_walk`), where
the contiguous kernel above walks one length for all rows. It takes a
tail of K1 tokens a row: one is a decode step, k+1 the verify step of
speculative decoding (`fused_paged_verify_step`).
"""

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.profiler.parts import part

NEG_INF = -1e30

# Per-generation VMEM capacity (MiB). The runtime exposes no VMEM
# attribute, so `device_kind` is the spec handle. On a TPU a kind that
# is not in the table is an error (add it, or set FLAGS_vmem_mib); off
# the TPU the kernels run only in interpret mode and plan for the v5e.
_VMEM_MIB_BY_KIND = {
    "TPU v4": 128,
    "TPU v5 lite": 128,     # v5e
    "TPU v5e": 128,
    "TPU v5": 128,          # v5p
    "TPU v5p": 128,
    "TPU v6 lite": 128,     # v6e / trillium
}
_VMEM_MIB_OFF_TPU = 128


def _vmem_mib() -> int:
    """VMEM capacity of device 0 in MiB (flag override > Mosaic probe >
    kind table).

    ``FLAGS_vmem_mib = -1`` runs the boot-time scoped-VMEM bisect probe
    (`ops/vmem_probe.py`, cached per device kind) instead of trusting the
    table. The probe's trivial kernel allocates 4 MiB less than hardware
    capacity (124 of 128 MiB on v5e — Mosaic's fixed reservations), so
    capacity = probed + 4; on v5e that reproduces the table value exactly,
    and the downstream `_vmem_budget/_vmem_limit` margins (which were
    calibrated against *real* fused kernels) stay meaningful.
    """
    from paddle_tpu.core.flags import flag
    override = int(flag("FLAGS_vmem_mib") or 0)
    if override > 0:
        return override
    dev = jax.devices()[0]
    if override == -1:
        from paddle_tpu.ops.vmem_probe import probe_usable_vmem_mib
        return probe_usable_vmem_mib(dev.device_kind) + 4
    if dev.platform != "tpu":
        return _VMEM_MIB_OFF_TPU
    if dev.device_kind not in _VMEM_MIB_BY_KIND:
        raise RuntimeError(
            f"no VMEM capacity known for TPU device_kind "
            f"{dev.device_kind!r}: add it to _VMEM_MIB_BY_KIND, or set "
            f"FLAGS_vmem_mib (MiB, or -1 to probe)")
    return _VMEM_MIB_BY_KIND[dev.device_kind]


def _vmem_budget_bytes() -> int:
    """Planning budget for double-buffered weight blocks: capacity minus
    40 MiB of headroom (KV chunks, scratch, Mosaic's own reservations —
    the margin probed on v5e where 88 of 128 MiB plans reliably)."""
    return max(48, _vmem_mib() - 40) * 2 ** 20


def _vmem_limit_bytes() -> int:
    """Scoped-VMEM limit passed to Mosaic: capacity minus 28 MiB (100 of
    128 MiB is the probed reliable ceiling on v5e)."""
    return max(64, _vmem_mib() - 28) * 2 ** 20


# ---------------------------------------------------------------------------
# Block planning (qkv column split + FFN column blocks)
# ---------------------------------------------------------------------------

# Bytes-equivalent cost of one extra grid step (~2 µs of per-step scalar
# overhead at v5e HBM bandwidth) — lets the planner trade zero-padding a
# non-128-multiple ffn (e.g. 11008 → 11264) against running many tiny
# blocks (fblk=256 would take 43 grid steps/layer on Llama-2-7B).
_GRID_STEP_BYTES = 3 * 2 ** 19


def _step_penalty(w_step):
    """Cost penalty for oversized per-grid-step weight blocks in the
    split (big-model) regime: blocks above ~30 MiB serialize DMA against
    compute — measured on llama2-7b int8 (SCALE.md r5 sweep: qs8/f512 at
    11.33 ms/step beats qs4/f1024 at 11.93 and qs6/f512 at 12.08)."""
    return max(0, 4 * (w_step - 28 * 2 ** 20))


def decode_block_plan(h: int, dqkv: int, dq: int, hd: int, ffn: int,
                      wbytes: int, q_split: Optional[int] = None,
                      cache_wbytes: int = 2) -> Dict:
    """Joint plan for the fused decode kernel's weight streaming.

    At 7B scale (h=4096) the attention weights alone (wqkv 50 MiB + wo
    17 MiB int8) cannot double-buffer in v5e's 128 MiB VMEM, so the qkv
    projection is split into `q_split` head-aligned COLUMN phases — each
    grid step streams one (h, qblk) block, mirroring how the FFN has
    always streamed in column blocks. FFN blocks are chosen from
    128-lane multiples (zero-padding ffn up to J*fblk when ffn isn't a
    128-multiple — SwiGLU pad columns contribute silu(0)*0 = 0 exactly),
    minimizing streamed bytes + grid-step overhead.

    Returns {"q_split", "qblk", "ffn_blocks", "fblk", "ffn_pad",
    "cache_wbytes"} where ffn_pad >= ffn is the padded column count
    build_fused_params must produce. `q_split` forces the split (tests).
    `cache_wbytes` records the KV-cache element size this plan assumed
    (1 = int8 cache mode); the kernel sizes its chunk scratch from the
    actual cache dtype and ASSERTS it agrees with the plan, so a stale
    bf16 plan can't silently drive an int8-cache decode (or vice versa).
    """
    budget = _vmem_budget_bytes()
    half = max((budget - 8 * 2 ** 20) // 2, 2 ** 20)
    nheads_tot = dqkv // hd

    def ffn_pick(fixed, fmax, split):
        # candidates: 128-multiples up to fmax (padding allowed) plus, for
        # non-128-multiple ffns, the exact divisors (no padding)
        if ffn <= 128:
            return (1, ffn, ffn) if ffn <= fmax else None
        cands = list(range(128, min(ffn + 127, fmax) + 1, 128))
        if split:
            # split (big-model) regime: only 512-multiples (+128/256)
            # stream cleanly — 640/768-lane blocks measured 8-200% slower
            # on the llama2-7b sweeps (SCALE.md r5)
            cands = [f for f in cands if f % 512 == 0 or f in (128, 256)]
        if not cands:
            # no lane-aligned block fits: exact divisors as a last resort
            cands = [f for f in range(1, min(ffn, fmax) + 1)
                     if ffn % f == 0]
        best = None
        for f in cands:
            jn = -(-ffn // f)
            cost = 3 * jn * f * h * wbytes + jn * _GRID_STEP_BYTES
            if split:
                cost += _step_penalty(fixed + 3 * f * h * wbytes)
            if best is None or cost < best[0] or (cost == best[0]
                                                  and f > best[2]):
                best = (cost, jn, f)
        return (best[1], best[2], best[1] * best[2]) if best else None

    best = None
    qs_list = ([q_split] if q_split else
               [q for q in range(1, nheads_tot + 1) if nheads_tot % q == 0])
    for qs in qs_list:
        qblk = dqkv // qs
        if qblk % hd:
            continue
        if qs > 1 and not q_split and (
                qblk % 128 or not (qblk % 512 == 0 or qblk in (128, 256))):
            continue    # lane-aligned, 512-multiple splits only (see
            # ffn_pick: 768-lane qkv blocks measured 3x slower)
        fixed = (qblk + dq) * h * wbytes
        pick = ffn_pick(fixed, (half - fixed) // (3 * h * wbytes), qs > 1)
        if pick is None:
            continue
        jn, fblk, pad = pick
        cost = (3 * pad * h * wbytes + jn * _GRID_STEP_BYTES
                + qs * _GRID_STEP_BYTES)
        if qs > 1:
            cost += _step_penalty(fixed + 3 * fblk * h * wbytes)
        if best is None or cost < best[0]:
            best = (cost, qs, qblk, jn, fblk, pad)
    if best is None:
        if q_split:
            raise ValueError(
                f"decode_block_plan: forced q_split={q_split} is invalid "
                f"for dqkv={dqkv}, hd={hd} under the current VMEM budget")
        # nothing fits the budget even maximally split: stream the finest
        # head-aligned qkv blocks + 128-col FFN blocks and let Mosaic cope
        qs = nheads_tot
        jn = -(-ffn // 128) if ffn > 128 else 1
        fblk = 128 if ffn > 128 else ffn
        best = (0, qs, hd, jn, fblk, jn * fblk)
    _, qs, qblk, jn, fblk, pad = best
    return {"q_split": qs, "qblk": qblk, "ffn_blocks": jn, "fblk": fblk,
            "ffn_pad": pad, "cache_wbytes": cache_wbytes}


def _pad_ffn(stacks: Dict[str, jax.Array], ffn_pad: int):
    """Zero-pad the FFN stacks' ffn dim up to ffn_pad (scales pad with 1;
    quantized pad weights are 0 so the scale value is inert)."""
    ffn = stacks["wg"].shape[2]
    if ffn_pad <= ffn:
        return stacks
    p = ffn_pad - ffn
    out = dict(stacks)
    for k in ("wg", "wu"):
        out[k] = jnp.pad(stacks[k], ((0, 0), (0, 0), (0, p)))
    out["wd"] = jnp.pad(stacks["wd"], ((0, 0), (0, p), (0, 0)))
    for k in ("wg_s", "wu_s"):
        if k in stacks:
            out[k] = jnp.pad(stacks[k], ((0, 0), (0, 0), (0, p)),
                             constant_values=1.0)
    return out


# ---------------------------------------------------------------------------
# Stacked parameter pytree
# ---------------------------------------------------------------------------

def build_fused_params(state: Dict[str, jax.Array], num_layers: int,
                       prefix: str = "model.layers.",
                       ffn_pad: int = 0) -> Dict[str, jax.Array]:
    """Stack a Llama-style flat state dict into per-layer-stacked arrays.

    Returns {ln1 (L,h), wqkv (L,h,(nh+2nkv)*hd), wo (L,nh*hd,h), ln2 (L,h),
    wg (L,h,ffn), wu (L,h,ffn), wd (L,ffn,h)}. The qkv projections are
    fused along the output dim (q|k|v) the way fused_multi_transformer's
    qkv_weight is packed.

    Weight-only-int8 states (paddle_tpu.quantization — keys `weight_q` +
    `weight_scale`) produce int8 weight stacks plus per-out-channel scale
    rows {wqkv_s (L,1,dqkv), wo_s, wg_s, wu_s, wd_s} — the
    fused_multi_transformer_int8 packing: the kernel streams int8 and
    scales the matmul OUTPUTS.
    """
    int8 = f"{prefix}0.self_attn.q_proj.weight_q" in state

    def layer(i, name):
        if int8:
            return (state[f"{prefix}{i}.{name}.weight_q"],
                    state[f"{prefix}{i}.{name}.weight_scale"])
        return state[f"{prefix}{i}.{name}.weight"], None

    cols = {"ln1": [], "wqkv": [], "wo": [], "ln2": [], "wg": [], "wu": [],
            "wd": []}
    scales = {k: [] for k in ("wqkv", "wo", "wg", "wu", "wd")}

    def put(key, w, sc):
        cols[key].append(w)
        if int8:
            scales[key].append(sc)

    for i in range(num_layers):
        cols["ln1"].append(state[f"{prefix}{i}.input_layernorm.weight"])
        qs = [layer(i, f"self_attn.{n}_proj") for n in ("q", "k", "v")]
        put("wqkv", jnp.concatenate([w for w, _ in qs], axis=1),
            jnp.concatenate([sc for _, sc in qs]) if int8 else None)
        put("wo", *layer(i, "self_attn.o_proj"))
        cols["ln2"].append(
            state[f"{prefix}{i}.post_attention_layernorm.weight"])
        put("wg", *layer(i, "mlp.gate_proj"))
        put("wu", *layer(i, "mlp.up_proj"))
        put("wd", *layer(i, "mlp.down_proj"))
    out = {k: jnp.stack(v) for k, v in cols.items()}
    if int8:
        for k, v in scales.items():
            out[f"{k}_s"] = jnp.stack(v).astype(jnp.float32)[:, None, :]
    if ffn_pad:
        out = _pad_ffn(out, ffn_pad)
    return out


def build_fused_params_gpt(state: Dict[str, jax.Array], num_layers: int,
                           prefix: str = "gpt.h.") -> Dict[str, jax.Array]:
    """GPT-block stacks: LayerNorm scale+bias, fused qkv (weight already
    packed 3h), biases on every projection, single GELU FFN."""
    g = lambda i, n: state[f"{prefix}{i}.{n}"]
    out = {
        "ln1": jnp.stack([g(i, "ln_1.weight") for i in range(num_layers)]),
        "ln1_b": jnp.stack([g(i, "ln_1.bias") for i in range(num_layers)]),
        "wqkv": jnp.stack([g(i, "attn.qkv_proj.weight")
                           for i in range(num_layers)]),
        "bqkv": jnp.stack([g(i, "attn.qkv_proj.bias")
                           for i in range(num_layers)]),
        "wo": jnp.stack([g(i, "attn.out_proj.weight")
                         for i in range(num_layers)]),
        "bo": jnp.stack([g(i, "attn.out_proj.bias")
                         for i in range(num_layers)]),
        "ln2": jnp.stack([g(i, "ln_2.weight") for i in range(num_layers)]),
        "ln2_b": jnp.stack([g(i, "ln_2.bias") for i in range(num_layers)]),
        "wg": jnp.stack([g(i, "fc_in.weight") for i in range(num_layers)]),
        "bg": jnp.stack([g(i, "fc_in.bias") for i in range(num_layers)]),
        "wd": jnp.stack([g(i, "fc_out.weight") for i in range(num_layers)]),
        "bd": jnp.stack([g(i, "fc_out.bias") for i in range(num_layers)]),
    }
    return out


def build_fused_params_moe(state: Dict[str, jax.Array], num_layers: int,
                           prefix: str = "model.layers.") -> Dict[str, jax.Array]:
    """Mixtral-block stacks: llama attention (ln1/wqkv/wo) + MoE FFN.

    Returns {ln1 (L,h), wqkv (L,h,dqkv), wo (L,dq,h), ln2 (L,h),
    gate (L,E,h) — the router projection TRANSPOSED so its lane dim is h
    (HBM lane dims want 128-multiples; E is typically 8), weg/weu
    (L,E,h,f), wed (L,E,f,h)}. The expert stacks stay in HBM; the kernel
    streams only the routed experts' weights per token (the TPU-native
    analog of the reference's fused MoE inference path —
    fused_multi_transformer + global_scatter composition).

    DeepSeekMoE shared experts (the model's concatenated `shared_mlp`)
    add dense stacks {wsg/wsu (L,h,ns·f), wsd (L,ns·f,h)} — every token
    uses them, so the kernel streams them like the llama FFN."""
    cols = {"ln1": [], "wqkv": [], "wo": [], "ln2": [], "gate": [],
            "weg": [], "weu": [], "wed": []}
    shared = f"{prefix}0.shared_mlp.gate_proj.weight" in state
    if shared:
        cols.update({"wsg": [], "wsu": [], "wsd": []})
    for i in range(num_layers):
        cols["ln1"].append(state[f"{prefix}{i}.input_layernorm.weight"])
        cols["wqkv"].append(jnp.concatenate(
            [state[f"{prefix}{i}.self_attn.{n}_proj.weight"]
             for n in ("q", "k", "v")], axis=1))
        cols["wo"].append(state[f"{prefix}{i}.self_attn.o_proj.weight"])
        cols["ln2"].append(
            state[f"{prefix}{i}.post_attention_layernorm.weight"])
        cols["gate"].append(state[f"{prefix}{i}.moe.gate.proj.weight"].T)
        cols["weg"].append(state[f"{prefix}{i}.moe.experts.w_gate"])
        cols["weu"].append(state[f"{prefix}{i}.moe.experts.w_up"])
        cols["wed"].append(state[f"{prefix}{i}.moe.experts.w_down"])
        if shared:
            cols["wsg"].append(state[f"{prefix}{i}.shared_mlp.gate_proj.weight"])
            cols["wsu"].append(state[f"{prefix}{i}.shared_mlp.up_proj.weight"])
            cols["wsd"].append(state[f"{prefix}{i}.shared_mlp.down_proj.weight"])
    return {k: jnp.stack(v) for k, v in cols.items()}


def quantize_kv_cache(kv, num_kv_heads: int):
    """Quantize a combined flat KV cache (L, b, S, 2*nkv*hd) to int8 with
    per-(layer, kv-head) symmetric scales — the fused_multi_transformer_int8
    cache_kv quant analog, calibrated from the cache contents themselves
    (prefill acts as the calibration pass; decode-appended tokens reuse the
    same static scales and clip outliers).

    Returns (cache int8, scales (L, 1, 2*nkv*hd) fp32) — the scales are
    lane-replicated across each head's hd lanes so both the kernel and the
    jnp reference can apply them with a single broadcast multiply (k-half
    scales fold into the q rows, v-half scales apply to the attention
    output)."""
    with part("attn"):
        L, b, S, dkv2 = kv.shape
        hd = dkv2 // (2 * num_kv_heads)
        amax = jnp.abs(kv.astype(jnp.float32)).max(axis=(1, 2))   # (L, 2dkv)
        amax = amax.reshape(L, 2 * num_kv_heads, hd).max(axis=-1)  # (L, 2nkv)
        scales = jnp.maximum(amax / 127.0, 1e-8)
        lanes = jnp.repeat(scales, hd, axis=-1)[:, None, :]       # (L,1,2dkv)
        q = jnp.clip(jnp.round(kv.astype(jnp.float32) / lanes[:, None]),
                     -127, 127)
        return q.astype(jnp.int8), lanes


def _layernorm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y.astype(w.dtype) * w + b)


def _rms(x, w, eps):
    """fp32 rms-normalize, cast to w.dtype path of ops.rms_norm."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = (xf * lax.rsqrt(var + eps))
    return (y.astype(w.dtype) * w)


def _rope1(x, cos, sin):
    """x (b, n, hd) fp32; cos/sin (1, 1, hd)."""
    hd = x.shape[-1]
    x1 = x[..., : hd // 2]
    x2 = x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rot * sin


# ---------------------------------------------------------------------------
# jnp reference (numerics twin + non-TPU fallback)
# ---------------------------------------------------------------------------

def fused_decode_reference(x, params, kv_cache, pos, cos, sin, *,
                           num_heads: int, num_kv_heads: int,
                           eps: float = 1e-5, arch: str = "llama",
                           top_k: int = 2, kv_scales=None):
    """One decode step through the whole stack; pure jnp.

    x (b, h); the KV cache is stored COMBINED and FLAT as
    (L, b, S, 2*nkv*hd) with k in lanes [0, nkv*hd) and v in the rest —
    the layout the Pallas kernel DMAs (one copy per chunk, lane dim a
    128-multiple); pos scalar int; cos/sin (1, hd) fp32 for position
    `pos`. Returns (x_out (b, h), kv_cache). Matches the Pallas kernel up
    to XLA fusion differences: residual stream fp32, attention over
    [0, pos] only (masked), softmax fp32.

    int8 KV cache mode: kv_cache int8 + `kv_scales` (L, 1, 2*nkv*hd) fp32
    (see quantize_kv_cache) — reads dequantize with the per-head scales,
    the appended token is quantized with the same static scales.
    """
    L, b, S, dkv2 = kv_cache.shape
    dkv = dkv2 // 2
    nh = num_heads
    nkv = num_kv_heads
    hd = dkv // nkv
    rep = nh // nkv
    dq = nh * hd
    dtype = x.dtype
    scale = 1.0 / math.sqrt(hd)
    int8 = "wqkv_s" in params
    cos_b = cos.reshape(1, 1, hd).astype(jnp.float32)
    sin_b = sin.reshape(1, 1, hd).astype(jnp.float32)

    def wdot(act, key, l):
        w = params[key][l]
        if int8:
            y = jnp.dot(act, w.astype(act.dtype),
                        preferred_element_type=jnp.float32)
            return y * params[f"{key}_s"][l]
        return jnp.dot(act, w, preferred_element_type=jnp.float32)

    gpt = arch == "gpt"
    xf = x.astype(jnp.float32)
    for l in range(L):
        if gpt:
            xn = _layernorm(xf, params["ln1"][l], params["ln1_b"][l], eps)
        else:
            xn = _rms(xf, params["ln1"][l], eps)
        qkv = wdot(xn, "wqkv", l)
        if gpt:
            qkv = qkv + params["bqkv"][l]
        q = qkv[:, :dq].reshape(b, nh, hd)
        k = qkv[:, dq:dq + nkv * hd].reshape(b, nkv, hd)
        v = qkv[:, dq + nkv * hd:].reshape(b, nkv, hd)
        if not gpt:
            q = _rope1(q, cos_b, sin_b)
            k = _rope1(k, cos_b, sin_b)
        kv_new = jnp.concatenate(
            [k.reshape(b, dkv), v.reshape(b, dkv)], axis=-1)
        if kv_scales is not None:       # int8 cache: quantize the append
            kv_new = jnp.clip(
                jnp.round(kv_new.astype(jnp.float32) / kv_scales[l]),
                -127, 127)
        kv_cache = lax.dynamic_update_slice(
            kv_cache, kv_new.astype(kv_cache.dtype)[None, :, None],
            (l, 0, pos, 0))
        kl = kv_cache[l, :, :, :dkv].astype(jnp.float32)
        vl = kv_cache[l, :, :, dkv:].astype(jnp.float32)
        if kv_scales is not None:       # dequantize with per-head scales
            kl = kl * kv_scales[l, :, :dkv][None]
            vl = vl * kv_scales[l, :, dkv:][None]
        kl = kl.reshape(b, S, nkv, hd)
        vl = vl.reshape(b, S, nkv, hd)
        qg = q.reshape(b, nkv, rep, hd) * scale
        scores = jnp.einsum("bgrd,bsgd->bgrs", qg, kl)
        valid = jnp.arange(S)[None, None, None] <= pos
        scores = jnp.where(valid, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bgrs,bsgd->bgrd", probs, vl)
        attn = attn.reshape(b, dq).astype(dtype)
        o = wdot(attn, "wo", l)
        if gpt:
            o = o + params["bo"][l]
        xf = xf + o
        if gpt:
            xn2 = _layernorm(xf, params["ln2"][l], params["ln2_b"][l], eps)
            g = wdot(xn2, "wg", l) + params["bg"][l]
            act = jax.nn.gelu(g, approximate=True).astype(dtype)
            xf = xf + wdot(act, "wd", l) + params["bd"][l]
        elif arch == "moe":
            # router math matches nn.layers.moe topk_routing: fp32 softmax
            # over the full expert set from the bf16 post-norm activations,
            # top-k renormalized. No-drop condition (b·k ≤ capacity) is
            # the fused path's eligibility gate, so `keep` is vacuous.
            xn2 = _rms(xf, params["ln2"][l], eps).astype(dtype)
            logits = jnp.dot(xn2.astype(jnp.float32),
                             params["gate"][l].astype(jnp.float32).T)
            probs = jax.nn.softmax(logits, axis=-1)
            vals, idx = lax.top_k(probs, top_k)            # (b, k)
            vals = vals / jnp.maximum(
                jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
            wg_sel = jnp.take(params["weg"][l], idx, axis=0)  # (b,k,h,f)
            wu_sel = jnp.take(params["weu"][l], idx, axis=0)
            wd_sel = jnp.take(params["wed"][l], idx, axis=0)  # (b,k,f,h)
            g = jnp.einsum("bh,bkhf->bkf", xn2, wg_sel,
                           preferred_element_type=jnp.float32)
            u = jnp.einsum("bh,bkhf->bkf", xn2, wu_sel,
                           preferred_element_type=jnp.float32)
            act = (jax.nn.silu(g) * u).astype(dtype)
            d = jnp.einsum("bkf,bkfh->bkh", act, wd_sel,
                           preferred_element_type=jnp.float32)
            xf = xf + jnp.einsum("bk,bkh->bh", vals, d)
            if "wsg" in params:   # DeepSeekMoE shared experts: dense SwiGLU
                sg = jnp.dot(xn2, params["wsg"][l],
                             preferred_element_type=jnp.float32)
                su = jnp.dot(xn2, params["wsu"][l],
                             preferred_element_type=jnp.float32)
                sact = (jax.nn.silu(sg) * su).astype(dtype)
                xf = xf + jnp.dot(sact, params["wsd"][l],
                                  preferred_element_type=jnp.float32)
        else:
            xn2 = _rms(xf, params["ln2"][l], eps)
            g = wdot(xn2, "wg", l)
            u = wdot(xn2, "wu", l)
            act = (jax.nn.silu(g) * u).astype(dtype)
            xf = xf + wdot(act, "wd", l)
    return xf.astype(dtype), kv_cache


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _pick_ffn_blocks(ffn: int, h: int, fixed_bytes: int, wbytes: int,
                     budget: Optional[int] = None):
    """Smallest J (ffn % J == 0) whose per-grid-step VMEM estimate —
    double-buffered weight blocks (attention weights + one FFN column
    block) on top of `fixed_bytes` of scratch — fits `budget` (default:
    derived from the device generation's VMEM, _vmem_budget_bytes)."""
    if budget is None:
        budget = _vmem_budget_bytes()
    for j in range(1, ffn + 1):
        if ffn % j:
            continue
        fblk = ffn // j
        weights = fixed_bytes + 3 * fblk * h * wbytes
        if 2 * weights + 8 * 2 ** 20 <= budget or fblk <= 128:
            return j, fblk
    return ffn, 1


def _fused_decode_pallas(x, params, kv_cache, pos, *,
                         num_heads: int, num_kv_heads: int, head_dim: int,
                         rope_base: float = 10000.0,
                         eps: float = 1e-5, chunk: int = 0,
                         arch: str = "llama", blocks: Optional[Dict] = None,
                         kv_scales=None, interpret: bool = False):
    # NOTE: not jit-wrapped — always invoked inside the caller's jit (the
    # generate() scan); a nested jit around a pallas_call trips XLA's
    # closed_call lowering cache.
    #
    # Mosaic layout rules shape this kernel (probed on v5e):
    #  * values cannot reshape the lane dim -> heads are split with lane
    #    SLICES (static, unrolled); attention batches ALL heads into one
    #    dot_general per KV block by staging q BLOCK-DIAGONALLY over the
    #    kv-group lane blocks (row n of q_s carries head n's rope'd q in
    #    its group's hd lanes, zeros elsewhere — zero lanes contract to
    #    exact 0 against the KV chunk, so one (b·nh)-row matmul replaces
    #    the old nkv unrolled per-group products)
    #  * DMA slices on the token (minor-2) dim must be 8-aligned -> the
    #    cache append is an aligned 8-token read-modify-write
    #  * HBM lane dims want 128-multiples -> the cache is stored flat as
    #    (L, b, S, nkv*hd)
    #  * bf16 relayouts through unit-dim inserts fail -> all merging math
    #    runs in fp32 with full-ref casts at the end
    #
    # int8 KV cache mode (kv_cache int8 + kv_scales (L, 1, 2*dkv) fp32):
    # chunks stream from HBM as int8 (half the cache DMA), dequantized on
    # the VMEM->MXU path — the k-half scales fold into the block-diagonal
    # q rows (one broadcast multiply), the v-half scales apply once to the
    # normalized attention output; the RMW append quantizes the new token
    # with the same static per-head scales.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, b, S, dkv2 = kv_cache.shape
    dkv = dkv2 // 2
    nh = num_heads
    nkv = num_kv_heads
    hd = head_dim
    assert hd == dkv // nkv
    rep = nh // nkv
    h = x.shape[1]
    dq = nh * hd
    dqkv = dq + 2 * dkv
    ffn = params["wg"].shape[2]          # ffn_pad when a plan padded it
    int8 = "wqkv_s" in params
    kvq = kv_scales is not None
    assert kvq == (jnp.dtype(kv_cache.dtype) == jnp.int8), \
        "int8 KV cache needs kv_scales (and vice versa)"
    gpt = arch == "gpt"
    wbytes = 1 if int8 else 2
    cb = jnp.dtype(kv_cache.dtype).itemsize
    if blocks is not None:
        Qs, qblk = blocks["q_split"], blocks["qblk"]
        J, fblk = blocks["ffn_blocks"], blocks["fblk"]
        assert ffn == J * fblk, (ffn, blocks)
        assert not (gpt and Qs > 1), "qkv split unsupported for arch=gpt"
        assert blocks.get("cache_wbytes", cb) == cb, \
            (f"decode plan assumed a {blocks['cache_wbytes']}-byte KV "
             f"cache but the cache dtype is {kv_cache.dtype} ({cb} B)")
    else:
        Qs, qblk = 1, dqkv
        J, fblk = _pick_ffn_blocks(
            ffn, h, fixed_bytes=(dqkv + nh * hd) * h * wbytes, wbytes=wbytes)
    if not chunk:
        chunk = 128
        if blocks is not None:
            # pick the KV chunk so weights + scratch fit the scoped-VMEM
            # ceiling. In the split regime ck=64 measured fastest on the
            # llama2-7b sweep (SCALE.md r5) — chunk DMA granularity
            # overlaps the weight stream better than maximal chunks.
            w2 = 2 * (qblk + dq + 3 * fblk) * h * wbytes
            # scratch: RMW block + kv32 staging + block-diagonal q_s and
            # the fori_loop-carried (b, nh, dkv) fp32 attention acc
            scratch_fixed = (b * 8 * 2 * dkv * cb + b * 2 * dkv * 4
                             + 2 * b * nh * dkv * 4 + b * h * 10)
            order = (64, 128, 32, 16, 8) if Qs > 1 else (128, 64, 32, 16, 8)
            for cand in order:
                if S % cand == 0 and (w2 + scratch_fixed + 6 * 2 ** 20
                                      + 2 * b * cand * 2 * dkv * cb
                                      <= _vmem_limit_bytes()):
                    chunk = cand
                    break
    ck = min(chunk, S)
    assert S % ck == 0, f"cache len {S} not a multiple of chunk {ck}"
    assert dkv % 128 == 0, f"nkv*hd={dkv} must be a lane multiple of 128"
    dtype = x.dtype
    scale = 1.0 / math.sqrt(hd)

    def kernel(*refs):
        if gpt:       # no gate weight: single GELU FFN matmul
            (pos_ref, x_in_ref, ln1_ref, wqkv_ref, wo_ref, ln2_ref,
             wg_ref, wd_ref) = refs[:8]
            wu_ref = None
            i = 8
        else:
            (pos_ref, x_in_ref, ln1_ref, wqkv_ref, wo_ref, ln2_ref,
             wg_ref, wu_ref, wd_ref) = refs[:9]
            i = 9
        if gpt:
            (ln1b_ref, ln2b_ref, bqkv_ref, bo_ref, bg_ref,
             bd_ref) = refs[i:i + 6]
            i += 6
        if int8:
            sqkv_ref, so_ref, sg_ref, su_ref, sd_ref = refs[i:i + 5]
            i += 5
        if kvq:
            kvs_ref = refs[i]            # (1, 2*dkv) per-head cache scales
            i += 1
        kv_in = refs[i]                  # aliased with kv_ref
        x_out_ref, kv_ref = refs[i + 1], refs[i + 2]
        (x_s, xn_s, acc_s, q_s, kv32_s, kvblk_s, kvch_s,
         wsem, rsem) = refs[i + 3:]
        del kv_in

        def wdot(act, wref, sref, rows=None):
            """act @ w with weight-only-int8 dequant folded onto the
            OUTPUT columns (per-out-channel scales) — the int8 stream
            converts to bf16 on the VMEM->MXU path, never touching HBM
            in bf16 (fused_multi_transformer_int8 semantics)."""
            w = wref[...] if rows is None else wref[rows, :]
            if int8:
                y = jnp.dot(act, w.astype(act.dtype),
                            preferred_element_type=jnp.float32)
                return y if sref is None else y * sref[...]
            return jnp.dot(act, w, preferred_element_type=jnp.float32)
        li = pl.program_id(0)
        j = pl.program_id(1)
        pos = pos_ref[0]

        def qkv_phase(p):
            # Phase p streams wqkv's column block p and stages its
            # head-aligned slices; the LAST phase also runs attention.
            # (Qs == 1 reproduces the original single attention phase.)
            blk = (pos // 8) * 8
            off = pos - blk

            def chunk_copy(c, slot):
                return pltpu.make_async_copy(
                    kv_ref.at[li, :, pl.ds(c * ck, ck)],
                    kvch_s.at[slot], rsem.at[slot])

            nc = (blk + ck - 1) // ck          # chunks covering [0, blk)
            if p == 0:
                # cache-append RMW block reads: layer 0 issues its own
                # (plus chunk 0); for later layers the previous layer's
                # first FFN step prefetched them
                @pl.when(li == 0)
                def _():
                    x_s[...] = x_in_ref[...].astype(jnp.float32)
                    # one-time zero of the block-diagonal q staging: every
                    # layer rewrites the same in-block lanes, so off-block
                    # lanes stay zero for the whole stack
                    q_s[...] = jnp.zeros_like(q_s)
                    pltpu.make_async_copy(
                        kv_ref.at[li, :, pl.ds(blk, 8)], kvblk_s,
                        wsem.at[0]).start()

                @pl.when((li == 0) & (nc > 0))
                def _():
                    chunk_copy(0, 0).start()

            if gpt:
                xn = _layernorm(x_s[...], ln1_ref[...].reshape(h),
                                ln1b_ref[...].reshape(h), eps)
            else:
                xn = _rms(x_s[...], ln1_ref[...].reshape(h), eps)
            part = wdot(xn, wqkv_ref, sqkv_ref if int8 else None)
            if gpt:
                part = part + bqkv_ref[...]
                rope2 = lambda t: t
            else:
                # rope angles computed in-kernel from pos (NeoX convention:
                # freqs repeated over both halves) — no XLA cos/sin table
                half = (lax.broadcasted_iota(jnp.int32, (1, hd), 1)
                        % (hd // 2)).astype(jnp.float32)
                inv_freq = jnp.exp(half * (-2.0 * math.log(rope_base) / hd))
                ang = pos.astype(jnp.float32) * inv_freq
                cos_b = jnp.cos(ang)
                sin_b = jnp.sin(ang)
                rope2 = lambda t: (t * cos_b + jnp.concatenate(
                    [-t[:, hd // 2:], t[:, :hd // 2]], axis=-1) * sin_b)
            # heads via lane slices (no lane reshapes): q staged BLOCK-
            # DIAGONALLY into (b, nh, dkv) f32 scratch — head n's rope'd,
            # pre-scaled q lands in its kv-group's hd lanes (row n, lanes
            # [g·hd, (g+1)·hd)) so attention runs as ONE dot_general per
            # KV block for all heads; new k/v staged FLAT (b, 2*dkv) f32
            # for the RMW merge. A column block may straddle the q|k|v
            # boundaries — qblk % hd == 0 keeps every slice head-aligned.
            for t in range(qblk // hd):
                col = p * qblk + t * hd
                seg = part[:, t * hd:(t + 1) * hd]
                if col < dq:
                    n = col // hd
                    g = n // rep
                    q_s[:, n, g * hd:(g + 1) * hd] = rope2(seg) * scale
                elif col < dq + dkv:
                    kv32_s[:, col - dq:col - dq + hd] = rope2(seg)
                else:
                    kv32_s[:, col - dq:col - dq + hd] = seg
            if p == Qs - 1:
                attention_tail(blk, off, chunk_copy, nc)

        def attention_tail(blk, off, chunk_copy, nc):
            # ---- online softmax, three stages sharing one set of
            # carries: (a) double-buffered chunk loop over the prefix
            # [0, blk) from HBM; (b) the freshly merged 8-token block
            # [blk, pos] straight from VMEM; stage (b) also hides the RMW
            # write-back behind the o-proj.
            rkb = pltpu.make_async_copy(
                kv_ref.at[li, :, pl.ds(blk, 8)], kvblk_s, wsem.at[0])

            # batched-head q: the block-diagonal (b, nh, dkv) staging; in
            # int8-cache mode the k-half dequant scales fold in here (one
            # broadcast multiply — off-block lanes are zero either way)
            if kvq:
                qbd = q_s[...] * kvs_ref[...][:, :dkv][None]
            else:
                qbd = q_s[...]

            def merge(carry, kvblk, idx, limit):
                """One online-softmax block update over ALL heads: kvblk
                (b, width, 2*dkv) in cache dtype; ONE score dot_general
                (block-diagonal q rows) + ONE weighted-value dot_general
                replace the old nkv unrolled per-group products."""
                m, l, acc = carry
                kf = kvblk[:, :, :dkv].astype(jnp.float32)
                vf = kvblk[:, :, dkv:].astype(jnp.float32)
                sc = lax.dot_general(
                    qbd, kf, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)      # (b, nh, w)
                sc = jnp.where(idx < limit, sc, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
                alpha = jnp.exp(m - m_new)
                pp = jnp.exp(sc - m_new[..., None])
                # row n of acc holds head n's weighted v in its group's
                # lane block (other lane blocks carry other groups' values
                # weighted with head n's probs — masked out at the o-proj)
                acc = acc * alpha[..., None] + lax.dot_general(
                    pp, vf, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)      # (b, nh, dkv)
                return m_new, l * alpha + jnp.sum(pp, axis=-1), acc

            def body(c, carry):
                slot = lax.rem(c, 2)

                @pl.when(c + 1 < nc)
                def _():
                    chunk_copy(c + 1, lax.rem(c + 1, 2)).start()

                chunk_copy(c, slot).wait()
                idx = c * ck + lax.broadcasted_iota(
                    jnp.int32, (1, 1, ck), 2)
                return merge(carry, kvch_s[slot], idx, blk)

            carry = lax.fori_loop(0, nc, body, (
                jnp.full((b, nh), NEG_INF, jnp.float32),
                jnp.zeros((b, nh), jnp.float32),
                jnp.zeros((b, nh, dkv), jnp.float32)))

            # merge the new token into the RMW block, attend to it from
            # VMEM, and write the block back (waited in FFN j==1)
            rkb.wait()
            sel = lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1) == off
            newtok = kv32_s[...]
            if kvq:         # quantize the append with the static scales
                newtok = jnp.clip(
                    jnp.round(newtok / kvs_ref[...]), -127.0, 127.0)
            kvblk_s[...] = jnp.where(
                sel, newtok[:, None, :],
                kvblk_s[...].astype(jnp.float32)).astype(kv_cache.dtype)
            wkb = pltpu.make_async_copy(
                kvblk_s, kv_ref.at[li, :, pl.ds(blk, 8)], wsem.at[0])
            wkb.start()
            bidx = blk + lax.broadcasted_iota(jnp.int32, (1, 1, 8), 2)
            ms, ls, accs = merge(carry, kvblk_s[...], bidx, pos + 1)

            norm = accs / ls[..., None]                     # (b, nh, dkv)
            if kvq:         # v-half dequant scales, applied once
                norm = norm * kvs_ref[...][:, dkv:][None]
            # o-proj without a lane-merge relayout:
            #  * MHA (rep == 1): rows and lane blocks are 1:1 — mask to
            #    the block diagonal and SUM over the head rows (adding
            #    exact zeros), collapsing to flat (b, dq) for ONE full
            #    matmul against wo
            #  * GQA (rep > 1): heads of a group share a lane block, so
            #    the sum would collide — one dot_general per kv group,
            #    batched over its rep heads against wo's row blocks
            if rep == 1:
                bd = (lax.broadcasted_iota(jnp.int32, (1, nh, dkv), 2)
                      // hd == lax.broadcasted_iota(
                          jnp.int32, (1, nh, dkv), 1))
                attn = jnp.sum(jnp.where(bd, norm, 0.0), axis=1)  # (b, dq)
                oacc = wdot(attn.astype(dtype), wo_ref,
                            so_ref if int8 else None)
            else:
                oacc = jnp.zeros((b, h), jnp.float32)
                for g in range(nkv):
                    ng = norm[:, g * rep:(g + 1) * rep,
                              g * hd:(g + 1) * hd]          # (b, rep, hd)
                    w3 = wo_ref[g * rep * hd:(g + 1) * rep * hd,
                                :].reshape(rep, hd, h)
                    part = lax.dot_general(
                        ng.astype(dtype),
                        w3.astype(dtype) if int8 else w3,
                        (((2,), (1,)), ((1,), (0,))),
                        preferred_element_type=jnp.float32)  # (rep, b, h)
                    oacc = oacc + jnp.sum(part, axis=0)
                if int8:
                    oacc = oacc * so_ref[...]
            if gpt:
                oacc = oacc + bo_ref[...]
            x = x_s[...] + oacc
            x_s[...] = x
            if gpt:
                xn_s[...] = _layernorm(x, ln2_ref[...].reshape(h),
                                       ln2b_ref[...].reshape(h),
                                       eps).astype(dtype)
            else:
                xn_s[...] = _rms(x, ln2_ref[...].reshape(h),
                                 eps).astype(dtype)
            acc_s[...] = jnp.zeros_like(acc_s)

        for p in range(Qs):
            pl.when(j == p)(functools.partial(qkv_phase, p))

        @pl.when(j >= Qs)
        def ffn_phase():
            @pl.when(j == Qs)
            def prefetch_next_layer():
                # drain this layer's cache write-back, then issue the next
                # layer's RMW-block + chunk-0 reads so its attention phase
                # never stalls on DMA latency
                blk = (pos // 8) * 8
                pltpu.make_async_copy(
                    kvblk_s, kv_ref.at[li, :, pl.ds(blk, 8)],
                    wsem.at[0]).wait()

                @pl.when(li + 1 < L)
                def _():
                    pltpu.make_async_copy(
                        kv_ref.at[li + 1, :, pl.ds(blk, 8)], kvblk_s,
                        wsem.at[0]).start()

                    @pl.when(blk > 0)
                    def _():
                        pltpu.make_async_copy(
                            kv_ref.at[li + 1, :, pl.ds(0, ck)],
                            kvch_s.at[0], rsem.at[0]).start()

            xn = xn_s[...]
            g = wdot(xn, wg_ref, sg_ref if int8 else None)
            if gpt:
                g = g + bg_ref[...]
                act = jax.nn.gelu(g, approximate=True).astype(dtype)
            else:
                u = wdot(xn, wu_ref, su_ref if int8 else None)
                act = (jax.nn.silu(g) * u).astype(dtype)
            acc_s[...] += wdot(act, wd_ref, sd_ref if int8 else None)

            if gpt:
                @pl.when(j == Qs + J - 1)
                def _():
                    acc_s[...] += jnp.broadcast_to(bd_ref[...], acc_s.shape)

            @pl.when(j == Qs + J - 1)
            def _():
                x = x_s[...] + acc_s[...]
                x_s[...] = x
                x_out_ref[...] = x.astype(dtype)

    def qi(jj):
        # qkv column block: phase j < Qs streams block j; FFN phases keep
        # the last block resident (no refetch)
        return jnp.minimum(jj, Qs - 1)

    def jm(ll, jj):
        # attention phases (j < Qs) reuse whatever the previous grid step
        # held (layer l-1's last FFN block) so they issue no FFN-weight
        # fetch; j >= Qs streams block j-Qs of layer l.
        return jnp.where(jj < Qs, J - 1, jj - Qs)

    def fl(ll, jj):
        return lax.max(ll - (jj < Qs), 0)
    grid = (L, Qs + J)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # pos
            pl.BlockSpec((b, h), lambda l, j: (0, 0)),             # x
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),    # ln1
            pl.BlockSpec((None, h, qblk),
                         lambda l, j: (l, 0, qi(j))),               # wqkv
            pl.BlockSpec((None, dq, h), lambda l, j: (l, 0, 0)),   # wo
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),    # ln2
            pl.BlockSpec((None, h, fblk),
                         lambda l, j: (fl(l, j), 0, jm(l, j))),     # wg
        ] + ([] if gpt else [
            pl.BlockSpec((None, h, fblk),
                         lambda l, j: (fl(l, j), 0, jm(l, j))),     # wu
        ]) + [
            pl.BlockSpec((None, fblk, h),
                         lambda l, j: (fl(l, j), jm(l, j), 0)),     # wd
        ] + ([
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # ln1_b
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # ln2_b
            pl.BlockSpec((None, 1, dqkv), lambda l, j: (l, 0, 0)),  # bqkv
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # bo
            pl.BlockSpec((None, 1, fblk),
                         lambda l, j: (fl(l, j), 0, jm(l, j))),     # bg
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # bd
        ] if gpt else []) + ([
            pl.BlockSpec((None, 1, qblk),
                         lambda l, j: (l, 0, qi(j))),               # sqkv
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # so
            pl.BlockSpec((None, 1, fblk),
                         lambda l, j: (fl(l, j), 0, jm(l, j))),     # sg
            pl.BlockSpec((None, 1, fblk),
                         lambda l, j: (fl(l, j), 0, jm(l, j))),     # su
            pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # sd
        ] if int8 else []) + ([
            pl.BlockSpec((None, 1, 2 * dkv), lambda l, j: (l, 0, 0)),  # kvs
        ] if kvq else []) + [
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),      # kv_cache
        ],
        out_specs=[
            pl.BlockSpec((b, h), lambda l, j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h), dtype),
            jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),          # x_s
            pltpu.VMEM((b, h), dtype),                # xn_s
            pltpu.VMEM((b, h), jnp.float32),          # acc_s
            pltpu.VMEM((b, nh, dkv), jnp.float32),    # q_s (block-diag)
            pltpu.VMEM((b, 2 * dkv), jnp.float32),    # kv32_s staging
            pltpu.VMEM((b, 8, 2 * dkv), kv_cache.dtype),   # kvblk_s RMW
            pltpu.VMEM((2, b, ck, 2 * dkv), kv_cache.dtype),  # kvch_s dbuf
            pltpu.SemaphoreType.DMA((1,)),            # wsem
            pltpu.SemaphoreType.DMA((2,)),            # rsem
        ],
        input_output_aliases={(9 - gpt + 6 * gpt + 5 * int8 + kvq): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the default 16 MiB scoped limit can't hold a layer's
            # double-buffered weights + KV chunks; raise to the device
            # generation's capacity minus headroom
            vmem_limit_bytes=_vmem_limit_bytes()),
        name="fused_decode_step",
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), x,
      params["ln1"][:, None], params["wqkv"],
      params["wo"], params["ln2"][:, None], params["wg"],
      *(() if gpt else (params["wu"],)),
      params["wd"],
      *((params["ln1_b"][:, None], params["ln2_b"][:, None],
         params["bqkv"][:, None], params["bo"][:, None],
         params["bg"][:, None], params["bd"][:, None]) if gpt else ()),
      *((params["wqkv_s"], params["wo_s"], params["wg_s"],
         params["wu_s"], params["wd_s"]) if int8 else ()),
      *((jnp.asarray(kv_scales, jnp.float32),) if kvq else ()),
      kv_cache)
    return out[0], out[1]



def _pick_expert_blocks(ffn: int, h: int, fixed_bytes: int, wbytes: int,
                        budget: Optional[int] = None, nbuf: int = 2):
    """Smallest J (ffn % J == 0, block a 128-lane multiple — expert-weight
    DMAs slice the lane dim) whose `nbuf`-buffered expert blocks fit the
    VMEM budget on top of `fixed_bytes` (nbuf=3 for the prefetch-two-ahead
    routed-expert pipeline)."""
    if budget is None:
        budget = _vmem_budget_bytes()
    best = None
    for j in range(1, ffn // 128 + 1):
        if ffn % j or (ffn // j) % 128:
            continue
        fblk = ffn // j
        need = fixed_bytes + nbuf * 3 * fblk * h * wbytes + 8 * 2 ** 20
        best = (j, fblk)              # smallest valid block so far
        if need <= budget:
            return j, fblk
    if best is None:
        raise ValueError(f"expert ffn {ffn} has no 128-multiple block")
    # Nothing fit the budget: fall back to the SMALLEST valid block (the
    # last candidate) — the one least likely to overflow VMEM.
    return best


def _fused_decode_moe_pallas(x, params, kv_cache, pos, *,
                             num_heads: int, num_kv_heads: int,
                             head_dim: int, top_k: int,
                             rope_base: float = 10000.0,
                             eps: float = 1e-5, chunk: int = 0,
                             blocks: Optional[Dict] = None,
                             kv_scales=None,
                             interpret: bool = False):
    """Fused MoE decode step: llama attention block + top-k expert FFN with
    DATA-DEPENDENT weight streaming.

    The llama/gpt kernel streams its FFN weights through Mosaic-pipelined
    BlockSpecs — impossible here because which expert's weights are needed
    is decided by the router *inside* the kernel. Instead the expert
    stacks stay in HBM (`pl.ANY`) and the kernel hand-rolls a
    PREFETCH-TWO-AHEAD async-copy pipeline over b·top_k slots per layer
    (3 VMEM buffers, copies for steps u+1 AND u+2 in flight while step u
    computes), fetching ONLY the routed experts' weights — decode is
    weight-bandwidth-bound, so per-token traffic drops from E experts to
    top_k (the TPU-native analog of the reference's fused MoE inference:
    fused_multi_transformer + global_scatter, SURVEY §2.2 fusion + §2.6
    EP). The depth-2 prefetch is the b=1 bubble fix (r5: 72% of
    roofline): with double buffering, slot u+1's weights were only
    requested when slot u's matmul began, so small b·k left the DMA
    engine idle across the slot turnaround; now the attention/router
    phase launches slots 0 and 1 together and every FFN step keeps two
    fetches in flight.

    Grid (L, 1 + Js + b·k·J): phase 0 = attention + router (argmax top-k
    into SMEM so the DMA engine can address expert slices); phases 1.. =
    one (row, choice, ffn-block) expert matmul each. Requires b·top_k ≤
    routing capacity (no-drop — the eligibility gate) and E % 8 == 0.

    int8 KV cache mode (kv_cache int8 + kv_scales (L, 1, 2*dkv) fp32 —
    see `quantize_kv_cache`): same folding as the llama/gpt kernel — the
    k-half scales fold into the block-diagonal q rows, the v-half scales
    apply once to the normalized attention output, and the RMW append
    quantizes the new token with the static per-head scales. `blocks`
    (a `decode_block_plan` dict) is consistency-checked: the plan's
    `cache_wbytes` must match the actual cache dtype, and the KV chunk
    is sized from the CACHE element size, so an int8 cache streams
    double-length chunks at unchanged chunk bytes.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, b, S, dkv2 = kv_cache.shape
    dkv = dkv2 // 2
    nh = num_heads
    nkv = num_kv_heads
    hd = head_dim
    assert hd == dkv // nkv
    rep = nh // nkv
    h = x.shape[1]
    dq = nh * hd
    dqkv = dq + 2 * dkv
    E = params["gate"].shape[1]
    ffn = params["weg"].shape[3]
    k = top_k
    nslots = b * k
    wbytes = 2
    kvq = kv_scales is not None
    assert kvq == (jnp.dtype(kv_cache.dtype) == jnp.int8), \
        "int8 KV cache needs kv_scales (and vice versa)"
    cb = jnp.dtype(kv_cache.dtype).itemsize
    if blocks is not None:
        assert blocks.get("cache_wbytes", cb) == cb, \
            (f"decode plan assumed a {blocks['cache_wbytes']}-byte KV "
             f"cache but the cache dtype is {kv_cache.dtype} ({cb} B)")
    shared = "wsg" in params
    fs = params["wsg"].shape[2] if shared else 0
    NBUF, PF = 3, 2        # prefetch-two-ahead triple-buffered pipeline
    # attention weights ride the Mosaic pipeline (double-buffered), expert
    # blocks ride the manual pipeline — both count against VMEM, as do the
    # block-diagonal q staging and the fori_loop-carried attention acc
    attn_fixed = 2 * (dqkv + dq + E) * h * wbytes + 2 * b * nh * dkv * 4
    J, fblk = _pick_expert_blocks(ffn, h, fixed_bytes=attn_fixed,
                                  wbytes=wbytes, nbuf=NBUF)
    if shared:
        # DeepSeekMoE dense shared experts: Mosaic-pipelined column
        # blocks like the llama FFN, budgeted AFTER the expert buffers
        Js, fsblk = _pick_expert_blocks(
            fs, h, fixed_bytes=attn_fixed + NBUF * 3 * fblk * h * wbytes,
            wbytes=wbytes)
    else:
        Js, fsblk = 0, 0
    nsteps = nslots * J
    if not chunk:
        # KV chunk sized from the CACHE element size: candidates are
        # equal-BYTE chunks, so the int8 cache (cb=1) streams 256-token
        # chunks where bf16 streamed 128 — half the DMA turnarounds on
        # the same chunk bytes (the cache_wbytes accounting the plan
        # records). Capped by the scoped-VMEM limit next to the
        # attention weights + expert buffers.
        chunk = 128
        wfix = (2 * (dqkv + dq + E) * h * wbytes
                + NBUF * 3 * fblk * h * wbytes
                + (2 * 3 * fsblk * h * wbytes if shared else 0))
        scratch_fixed = (b * 8 * 2 * dkv * cb + b * 2 * dkv * 4
                         + 2 * b * nh * dkv * 4 + b * h * 10)
        order = (256, 128, 64, 32, 16, 8) if cb == 1 else \
            (128, 64, 32, 16, 8)
        for cand in order:
            if S % cand == 0 and (wfix + scratch_fixed + 6 * 2 ** 20
                                  + 2 * b * cand * 2 * dkv * cb
                                  <= _vmem_limit_bytes()):
                chunk = cand
                break
    ck = min(chunk, S)
    assert S % ck == 0, f"cache len {S} not a multiple of chunk {ck}"
    assert dkv % 128 == 0, f"nkv*hd={dkv} must be a lane multiple of 128"
    assert E % 8 == 0, f"num_experts {E} must be a multiple of 8"
    dtype = x.dtype
    scale = 1.0 / math.sqrt(hd)

    def kernel(*refs):
        (pos_ref, x_in_ref, ln1_ref, wqkv_ref, wo_ref, ln2_ref,
         gate_ref, weg_ref, weu_ref, wed_ref) = refs[:10]
        i = 10
        if shared:
            wsg_ref, wsu_ref, wsd_ref = refs[i:i + 3]
            i += 3
        if kvq:
            kvs_ref = refs[i]            # (1, 2*dkv) per-head cache scales
            i += 1
        kv_in = refs[i]
        x_out_ref, kv_ref = refs[i + 1], refs[i + 2]
        (x_s, xn_s, acc_s, q_s, kv32_s, kvblk_s, kvch_s,
         wsem, rsem, eid_s, egw_s, ewg_s, ewu_s, ewd_s, esem) = refs[i + 3:]
        del kv_in
        li = pl.program_id(0)
        t = pl.program_id(1)
        pos = pos_ref[0]

        def expert_copies(u, buf):
            """The three async copies streaming step-u's expert block."""
            s = u // J
            jj = u % J
            r = s // k
            c = s % k
            eid = eid_s[r, c]
            if J == 1:
                src_g = weg_ref.at[li, eid]
                src_u = weu_ref.at[li, eid]
                src_d = wed_ref.at[li, eid]
            else:
                src_g = weg_ref.at[li, eid, :, pl.ds(jj * fblk, fblk)]
                src_u = weu_ref.at[li, eid, :, pl.ds(jj * fblk, fblk)]
                src_d = wed_ref.at[li, eid, pl.ds(jj * fblk, fblk), :]
            return (
                pltpu.make_async_copy(src_g, ewg_s.at[buf], esem.at[buf, 0]),
                pltpu.make_async_copy(src_u, ewu_s.at[buf], esem.at[buf, 1]),
                pltpu.make_async_copy(src_d, ewd_s.at[buf], esem.at[buf, 2]),
            )

        @pl.when(t == 0)
        def attention_phase():
            @pl.when(li == 0)
            def _():
                x_s[...] = x_in_ref[...].astype(jnp.float32)
                # one-time zero of the block-diagonal q staging (layers
                # rewrite the same in-block lanes; off-block lanes stay 0)
                q_s[...] = jnp.zeros_like(q_s)

            blk = (pos // 8) * 8
            off = pos - blk
            rkb = pltpu.make_async_copy(
                kv_ref.at[li, :, pl.ds(blk, 8)], kvblk_s, wsem.at[0])

            @pl.when(li == 0)
            def _():
                rkb.start()

            xn = _rms(x_s[...], ln1_ref[...].reshape(h), eps)
            qkv = jnp.dot(xn, wqkv_ref[...],
                          preferred_element_type=jnp.float32)
            half = (lax.broadcasted_iota(jnp.int32, (1, hd), 1)
                    % (hd // 2)).astype(jnp.float32)
            inv_freq = jnp.exp(half * (-2.0 * math.log(rope_base) / hd))
            ang = pos.astype(jnp.float32) * inv_freq
            cos_b = jnp.cos(ang)
            sin_b = jnp.sin(ang)
            rope2 = lambda v: (v * cos_b + jnp.concatenate(
                [-v[:, hd // 2:], v[:, :hd // 2]], axis=-1) * sin_b)
            # q staged block-diagonally over kv-group lane blocks (see
            # _fused_decode_pallas): one dot_general per KV block for all
            # heads instead of nkv unrolled per-group products
            for n in range(nh):
                g = n // rep
                q_s[:, n, g * hd:(g + 1) * hd] = rope2(
                    qkv[:, n * hd:(n + 1) * hd]) * scale
            for g in range(nkv):
                kv32_s[:, g * hd:(g + 1) * hd] = rope2(
                    qkv[:, dq + g * hd:dq + (g + 1) * hd])
                kv32_s[:, dkv + g * hd:dkv + (g + 1) * hd] = \
                    qkv[:, dq + dkv + g * hd:dq + dkv + (g + 1) * hd]

            def chunk_copy(c, slot):
                return pltpu.make_async_copy(
                    kv_ref.at[li, :, pl.ds(c * ck, ck)],
                    kvch_s.at[slot], rsem.at[slot])

            # batched-head q; in int8-cache mode the k-half dequant
            # scales fold in here (one broadcast multiply — off-block
            # lanes are zero either way)
            if kvq:
                qbd = q_s[...] * kvs_ref[...][:, :dkv][None]
            else:
                qbd = q_s[...]

            def merge(carry, kvblk, idx, limit):
                m, l, acc = carry
                kf = kvblk[:, :, :dkv].astype(jnp.float32)
                vf = kvblk[:, :, dkv:].astype(jnp.float32)
                sc = lax.dot_general(
                    qbd, kf, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)      # (b, nh, w)
                sc = jnp.where(idx < limit, sc, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
                alpha = jnp.exp(m - m_new)
                pp = jnp.exp(sc - m_new[..., None])
                acc = acc * alpha[..., None] + lax.dot_general(
                    pp, vf, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)      # (b, nh, dkv)
                return m_new, l * alpha + jnp.sum(pp, axis=-1), acc

            nc = (blk + ck - 1) // ck

            @pl.when((li == 0) & (nc > 0))
            def _():
                chunk_copy(0, 0).start()

            def body(c, carry):
                slot = lax.rem(c, 2)

                @pl.when(c + 1 < nc)
                def _():
                    chunk_copy(c + 1, lax.rem(c + 1, 2)).start()

                chunk_copy(c, slot).wait()
                idx = c * ck + lax.broadcasted_iota(
                    jnp.int32, (1, 1, ck), 2)
                return merge(carry, kvch_s[slot], idx, blk)

            carry = lax.fori_loop(0, nc, body, (
                jnp.full((b, nh), NEG_INF, jnp.float32),
                jnp.zeros((b, nh), jnp.float32),
                jnp.zeros((b, nh, dkv), jnp.float32)))

            rkb.wait()
            sel = lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1) == off
            newtok = kv32_s[...]
            if kvq:         # quantize the append with the static scales
                newtok = jnp.clip(
                    jnp.round(newtok / kvs_ref[...]), -127.0, 127.0)
            kvblk_s[...] = jnp.where(
                sel, newtok[:, None, :],
                kvblk_s[...].astype(jnp.float32)).astype(kv_cache.dtype)
            wkb = pltpu.make_async_copy(
                kvblk_s, kv_ref.at[li, :, pl.ds(blk, 8)], wsem.at[0])
            wkb.start()
            bidx = blk + lax.broadcasted_iota(jnp.int32, (1, 1, 8), 2)
            ms, ls, accs = merge(carry, kvblk_s[...], bidx, pos + 1)

            norm = accs / ls[..., None]                     # (b, nh, dkv)
            if kvq:         # v-half dequant scales, applied once
                norm = norm * kvs_ref[...][:, dkv:][None]
            if rep == 1:
                bd = (lax.broadcasted_iota(jnp.int32, (1, nh, dkv), 2)
                      // hd == lax.broadcasted_iota(
                          jnp.int32, (1, nh, dkv), 1))
                attn = jnp.sum(jnp.where(bd, norm, 0.0), axis=1)
                oacc = jnp.dot(attn.astype(dtype), wo_ref[...],
                               preferred_element_type=jnp.float32)
            else:
                oacc = jnp.zeros((b, h), jnp.float32)
                for g in range(nkv):
                    ng = norm[:, g * rep:(g + 1) * rep,
                              g * hd:(g + 1) * hd]          # (b, rep, hd)
                    w3 = wo_ref[g * rep * hd:(g + 1) * rep * hd,
                                :].reshape(rep, hd, h)
                    part = lax.dot_general(
                        ng.astype(dtype), w3,
                        (((2,), (1,)), ((1,), (0,))),
                        preferred_element_type=jnp.float32)  # (rep, b, h)
                    oacc = oacc + jnp.sum(part, axis=0)
            xr = x_s[...] + oacc
            x_s[...] = xr
            xn2 = _rms(xr, ln2_ref[...].reshape(h), eps).astype(dtype)
            xn_s[...] = xn2

            # ---- router (fp32, matches nn.layers.moe topk_routing):
            # softmax over E, sequential argmax top-k (= lax.top_k's
            # lowest-index tie-breaking), renormalized weights. Ids land
            # in SMEM so the expert-weight DMAs can address them.
            logits = lax.dot_general(
                xn2.astype(jnp.float32), gate_ref[...].astype(jnp.float32),
                (((1,), (1,)), ((), ())))                   # (b, E)
            mx = jnp.max(logits, axis=-1, keepdims=True)
            ex = jnp.exp(logits - mx)
            probs = ex / jnp.sum(ex, axis=-1, keepdims=True)
            cur = probs
            vals = []
            eidx = lax.broadcasted_iota(jnp.int32, (b, E), 1)
            for c in range(k):
                v_c = jnp.max(cur, axis=-1)                 # (b,)
                a_c = jnp.argmax(cur, axis=-1).astype(jnp.int32)
                vals.append(v_c)
                for r in range(b):
                    eid_s[r, c] = a_c[r]
                cur = jnp.where(eidx == a_c[:, None], NEG_INF, cur)
            tot = vals[0]
            for c in range(1, k):
                tot = tot + vals[c]
            tot = jnp.maximum(tot, 1e-9)
            for c in range(k):
                egw_s[:, c] = vals[c] / tot
            acc_s[...] = jnp.zeros_like(acc_s)
            # prime the prefetch-two-ahead pipeline: steps 0 AND 1 go out
            # together, so slot 1's weights stream during the shared-FFN
            # phases and slot 0's matmul instead of waiting for slot 0 to
            # finish (the b=1 slot-turnaround bubble)
            for cp in expert_copies(0, 0):
                cp.start()
            if nsteps > 1:
                for cp in expert_copies(1, 1):
                    cp.start()

        @pl.when(t == 1)
        def prefetch_next_layer():
            blk = (pos // 8) * 8
            pltpu.make_async_copy(
                kvblk_s, kv_ref.at[li, :, pl.ds(blk, 8)],
                wsem.at[0]).wait()

            @pl.when(li + 1 < L)
            def _():
                pltpu.make_async_copy(
                    kv_ref.at[li + 1, :, pl.ds(blk, 8)], kvblk_s,
                    wsem.at[0]).start()

                @pl.when(blk > 0)
                def _():
                    pltpu.make_async_copy(
                        kv_ref.at[li + 1, :, pl.ds(0, ck)],
                        kvch_s.at[0], rsem.at[0]).start()

        if shared:
            # DeepSeekMoE shared experts: dense SwiGLU column blocks
            # (Mosaic-pipelined BlockSpecs, weight 1.0, ALL rows) — the
            # routed experts' slot-0 DMAs overlap these phases
            @pl.when((t > 0) & (t <= Js))
            def shared_phase():
                xn = xn_s[...]
                g = jnp.dot(xn, wsg_ref[...],
                            preferred_element_type=jnp.float32)
                u = jnp.dot(xn, wsu_ref[...],
                            preferred_element_type=jnp.float32)
                act = (jax.nn.silu(g) * u).astype(dtype)
                acc_s[...] += jnp.dot(act, wsd_ref[...],
                                      preferred_element_type=jnp.float32)

        @pl.when(t > Js)
        def ffn_phase():
            u = t - 1 - Js
            buf = lax.rem(u, NBUF)

            for cp in expert_copies(u, buf):
                cp.wait()

            # steps u+1's copies are already in flight (issued at step
            # u-1, or primed by the router phase); top up the pipeline
            # with step u+PF. Buffer (u+PF) % NBUF was last read at step
            # u-1 (NBUF = PF+1), which this sequential grid has finished.
            @pl.when(u + PF < nsteps)
            def _():
                for cp in expert_copies(u + PF, lax.rem(u + PF, NBUF)):
                    cp.start()

            s = u // J
            r = s // k
            c = s % k
            xn = xn_s[...]
            g = jnp.dot(xn, ewg_s[buf],
                        preferred_element_type=jnp.float32)
            uu = jnp.dot(xn, ewu_s[buf],
                         preferred_element_type=jnp.float32)
            act = (jax.nn.silu(g) * uu).astype(dtype)
            d = jnp.dot(act, ewd_s[buf],
                        preferred_element_type=jnp.float32)   # (b, h)
            # select row r's contribution weighted by its gate value —
            # all-rows matmul + mask avoids dynamic scratch indexing
            # (b ≤ capacity/k is small; decode is bandwidth-bound)
            rmask = lax.broadcasted_iota(jnp.int32, (b, k), 0) == r
            cmask = lax.broadcasted_iota(jnp.int32, (b, k), 1) == c
            wsel = jnp.sum(jnp.where(rmask & cmask, egw_s[...], 0.0))
            rowmask = lax.broadcasted_iota(jnp.int32, (b, 1), 0) == r
            acc_s[...] += jnp.where(rowmask, d * wsel, 0.0)

            @pl.when(t == Js + nsteps)
            def _():
                xr = x_s[...] + acc_s[...]
                x_s[...] = xr
                x_out_ref[...] = xr.astype(dtype)

    def sjm(ll, tt):
        # shared-FFN column block: phases 1..Js stream blocks 0..Js-1;
        # t==0 keeps the previous layer's last block (no refetch), expert
        # phases keep the last block resident
        return jnp.where(tt < 1, Js - 1, jnp.minimum(tt - 1, Js - 1))

    def sl(ll, tt):
        return lax.max(ll - (tt < 1), 0)

    grid = (L, 1 + Js + nsteps)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # pos
            pl.BlockSpec((b, h), lambda l, t: (0, 0)),             # x
            pl.BlockSpec((None, 1, h), lambda l, t: (l, 0, 0)),    # ln1
            pl.BlockSpec((None, h, dqkv), lambda l, t: (l, 0, 0)),  # wqkv
            pl.BlockSpec((None, dq, h), lambda l, t: (l, 0, 0)),   # wo
            pl.BlockSpec((None, 1, h), lambda l, t: (l, 0, 0)),    # ln2
            pl.BlockSpec((None, E, h), lambda l, t: (l, 0, 0)),    # gate
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),      # weg
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),      # weu
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),      # wed
        ] + ([
            pl.BlockSpec((None, h, fsblk),
                         lambda l, t: (sl(l, t), 0, sjm(l, t))),    # wsg
            pl.BlockSpec((None, h, fsblk),
                         lambda l, t: (sl(l, t), 0, sjm(l, t))),    # wsu
            pl.BlockSpec((None, fsblk, h),
                         lambda l, t: (sl(l, t), sjm(l, t), 0)),    # wsd
        ] if shared else []) + ([
            pl.BlockSpec((None, 1, 2 * dkv), lambda l, t: (l, 0, 0)),  # kvs
        ] if kvq else []) + [
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),      # kv_cache
        ],
        out_specs=[
            pl.BlockSpec((b, h), lambda l, t: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h), dtype),
            jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),          # x_s
            pltpu.VMEM((b, h), dtype),                # xn_s
            pltpu.VMEM((b, h), jnp.float32),          # acc_s
            pltpu.VMEM((b, nh, dkv), jnp.float32),    # q_s (block-diag)
            pltpu.VMEM((b, 2 * dkv), jnp.float32),    # kv32_s
            pltpu.VMEM((b, 8, 2 * dkv), kv_cache.dtype),   # kvblk_s
            pltpu.VMEM((2, b, ck, 2 * dkv), kv_cache.dtype),  # kvch_s
            pltpu.SemaphoreType.DMA((1,)),            # wsem
            pltpu.SemaphoreType.DMA((2,)),            # rsem
            pltpu.SMEM((b, k), jnp.int32),            # eid_s
            pltpu.VMEM((b, k), jnp.float32),          # egw_s
            pltpu.VMEM((NBUF, h, fblk), dtype),       # ewg_s
            pltpu.VMEM((NBUF, h, fblk), dtype),       # ewu_s
            pltpu.VMEM((NBUF, fblk, h), dtype),       # ewd_s
            pltpu.SemaphoreType.DMA((NBUF, 3)),       # esem
        ],
        input_output_aliases={10 + 3 * shared + kvq: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes()),
        name="fused_decode_moe_step",
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), x,
      params["ln1"][:, None], params["wqkv"], params["wo"],
      params["ln2"][:, None], params["gate"],
      params["weg"], params["weu"], params["wed"],
      *((params["wsg"], params["wsu"], params["wsd"]) if shared else ()),
      *((jnp.asarray(kv_scales, jnp.float32),) if kvq else ()),
      kv_cache)
    return out[0], out[1]


def fused_decode_step(x, params, kv_cache, pos, cos, sin, *,
                      num_heads: int, num_kv_heads: int, eps: float = 1e-5,
                      rope_base: float = 10000.0, arch: str = "llama",
                      top_k: int = 2, blocks: Optional[Dict] = None,
                      kv_scales=None, kv_chunk: int = 0):
    """Dispatch: Pallas whole-stack kernel on TPU, jnp reference elsewhere.

    Args follow fused_decode_reference (combined flat KV cache). `pos` may
    be traced (it is the scan counter inside `inference.generate`).
    `top_k` applies to arch="moe" only. `blocks` is a `decode_block_plan`
    dict (the plan that padded the params must also drive the kernel; for
    arch="moe" only its `cache_wbytes` is consumed — consistency-checked
    against the cache dtype). `kv_scales` enables the int8 KV-cache mode
    (all three archs; see quantize_kv_cache). `kv_chunk` overrides the
    kernel's KV-chunk sizing (0 = let the kernel pick) — the OOM
    degradation ladder in `inference.generate` retries with a halved
    chunk, shrinking the double-buffered VMEM chunk scratch; the jnp
    reference path ignores it (no chunking to size).

    FLAGS_pallas_interpret=1 routes the Pallas kernel through interpret
    mode off-TPU — the CPU-CI path for kernel-logic parity tests.
    """
    from paddle_tpu.core.flags import flag
    from paddle_tpu.ops import use_pallas
    dkv = kv_cache.shape[-1] // 2
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    interp = bool(flag("FLAGS_pallas_interpret")) and not use_pallas()
    if (use_pallas() or interp) and dkv % 128 == 0 \
            and kv_cache.shape[2] % 128 == 0:
        # plan/cache consistency is a contract error with a message of
        # its own. (The reference path ignores `blocks` — an f32 cache
        # on a non-kernel backend stays valid.)
        cb = jnp.dtype(kv_cache.dtype).itemsize
        if blocks is not None and blocks.get("cache_wbytes", cb) != cb:
            raise ValueError(
                f"decode plan assumed a {blocks['cache_wbytes']}-byte KV "
                f"cache but the cache dtype is {kv_cache.dtype} ({cb} B); "
                f"rebuild the plan with decode_block_plan(cache_wbytes="
                f"{cb})")
        if arch == "moe":
            with part("layers"):
                return _fused_decode_moe_pallas(
                    x, params, kv_cache, pos,
                    num_heads=num_heads, num_kv_heads=num_kv_heads,
                    head_dim=dkv // num_kv_heads, top_k=top_k,
                    rope_base=rope_base, eps=eps, chunk=kv_chunk,
                    blocks=blocks, kv_scales=kv_scales,
                    interpret=interp)
        with part("layers"):
            return _fused_decode_pallas(
                x, params, kv_cache, pos,
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=dkv // num_kv_heads,
                rope_base=rope_base, eps=eps, chunk=kv_chunk,
                arch=arch, blocks=blocks,
                kv_scales=kv_scales, interpret=interp)
    with part("layers"):
        return fused_decode_reference(
            x, params, kv_cache, pos, cos, sin,
            num_heads=num_heads, num_kv_heads=num_kv_heads, eps=eps,
            arch=arch, top_k=top_k, kv_scales=kv_scales)


# ---------------------------------------------------------------------------
# Paged decode (continuous-batching serving): block-table KV pool
# ---------------------------------------------------------------------------
#
# The contiguous (L, b, S, 2*nkv*hd) cache above sizes every slot for
# prompt+max_new — a request that finishes early strands its tail, and a
# batch pads every slot to the longest member. The serving engine
# (paddle_tpu.serving) instead carves the cache into fixed-size KV BLOCKS
# shared by all slots (the vLLM paged-KV layout on the fused kernel):
#
#   kv_pool       (L, num_blocks, block_tokens, 2*nkv*hd)   HBM, aliased
#   block_tables  (b, max_blocks) int32   slot-local chunk c -> physical
#                                         block (layer-invariant: block n
#                                         holds the same token span in
#                                         every layer's pool plane)
#   positions     (b,) int32              per-slot append position
#
# One block == one KV chunk of the kernel's online-softmax walk, so the
# chunk copy indexes through the block table (the same SMEM-addressed DMA
# technique the MoE kernel uses for routed expert weights). Slots of
# wildly different lengths share one dispatch because the kernel walks a
# FLAT list of (row, chunk) pairs (`paged_walk`): each row's own blocks
# and nothing else, so a step reads the cache that is live. A verify step
# is the same kernel and the same walk, with a tail of k+1 tokens a row.


# Block buffers of the paged kernel's walk: a pair's DMA is started
# `_WALK_RING - 1` pairs ahead of its merge. Chosen on the chip (PERF.md
# §6, PR 28).
_WALK_RING = 4


def paged_walk_blocks(positions, block_tokens: int):
    """How many blocks of each row the paged decode kernel's walk covers.

    Row r holds ``positions[r]`` cached tokens; the walk covers its whole
    groups of 8 (the open group is read, merged with the new token and
    written back apart from the walk): ``nc_r = ceil((positions[r] // 8
    * 8) / block_tokens)`` blocks, none for a row at position 0 (an idle
    slot). Returns ``(nc, total, dense)``: the counts, their sum (the
    length of `paged_walk`'s list) and ``rows x max(nc)``, which is what
    a walk of every row to the longest row's length would cover; a full
    batch of equal rows gives ``total == dense``.

    ``positions`` as a numpy array gives numpy results (the serving
    engine's counters, from its host mirror); anything else is traced
    with ``jnp`` (the kernel's wrapper, inside the step program).
    """
    xp = np if isinstance(positions, np.ndarray) else jnp
    pos = xp.asarray(positions, xp.int32).reshape(-1)
    nc = (pos // 8 * 8 + block_tokens - 1) // block_tokens
    return nc, nc.sum(), pos.shape[0] * nc.max()


def paged_walk(positions, block_tokens: int, max_blocks: int):
    """The paged decode kernel's walk over cached KV, as a flat work list:
    the pairs (r, c) for c < nc_r (`paged_walk_blocks`), row-major.

    Returns ``(work_row, work_chunk, total)``: pair t is ``(work_row[t],
    work_chunk[t])`` for ``t < total``, in int32 arrays of the fixed
    length ``rows x max_blocks`` (entries from ``total`` on are in range
    and never walked). A full batch of equal rows gives the dense walk's
    pairs. numpy in, numpy out, as in `paged_walk_blocks`.
    """
    nc, _, _ = paged_walk_blocks(positions, block_tokens)
    return walk_list(nc, nc.shape[0] * max_blocks)


def walk_list(counts, length: int):
    """The flat work list of ``counts[r]`` items a row, row-major: item
    t is ``(row[t], chunk[t])`` with ``chunk[t] < counts[row[t]]``.

    Returns ``(row, chunk, total)``: int32 arrays of the fixed ``length``
    (at least ``counts.sum()``; entries from ``total`` on are in range and
    never walked) and ``total = counts.sum()``. numpy in, numpy out;
    anything else is traced with ``jnp``.
    """
    xp = np if isinstance(counts, np.ndarray) else jnp
    b = counts.shape[0]
    end = xp.cumsum(counts)
    # item t belongs to the first row whose running sum passes t; one
    # masked reduction over (items, rows) each, no search loop, no gather
    t = xp.arange(length)
    before = t[:, None] >= end[None, :]
    row = xp.minimum(before.sum(1), b - 1)
    chunk = xp.where(before[:, -1], 0, t - (before * counts[None, :]).sum(1))
    return row.astype(xp.int32), chunk.astype(xp.int32), counts.sum()


def paged_pool_shape(num_layers: int, num_blocks: int, block_tokens: int,
                     num_kv_heads: int, head_dim: int):
    """Shape of the paged KV pool (the serving engine's one cache tensor)."""
    return (num_layers, num_blocks, block_tokens,
            2 * num_kv_heads * head_dim)


# ---------------------------------------------------------------------------
# Tensor-parallel (mp) shard layouts for the paged serving path
# ---------------------------------------------------------------------------
#
# The serving engine shards ONE replica over the `mp` mesh axis by
# splitting attention heads (KV groups) and ffn columns across shards —
# column-parallel qkv/gate/up, with the o-proj and down-proj matmuls
# kept FULL on every shard behind one `all_gather` each. That flavor
# (gather the (b, cols) activation instead of psum-ing the (b, h)
# partial outputs) is what makes the sharded engine BIT-IDENTICAL to
# the single-chip engine: an all_gather is pure data movement, so the
# wo/wd matmuls see exactly the mp=1 operand and reduce in exactly the
# mp=1 order, while a psum would re-associate the h-dim reduction.
#
# Shard-major column permutations: the fused canonical layouts
# interleave regions ([q|k|v] for wqkv, [k|v] for the pool's last dim),
# so a plain contiguous split of the canonical columns would hand each
# shard a slice CROSSING region boundaries. The device twins are
# permuted SHARD-MAJOR instead — shard s's slice is itself a valid
# canonical layout at the local head counts — while host mirrors stay
# canonical (snapshots and parity pins never see the permutation).
# Because the reference q-head order is group-major (q.reshape(b, nkv,
# rep, hd)), sharding KV groups contiguously gives each shard a
# contiguous q-head range, so the tiled all_gather below reproduces the
# exact reference (b, dq) column order.

def mp_qkv_permutation(num_heads: int, num_kv_heads: int, head_dim: int,
                       mp: int):
    """Column permutation (len (nh+2*nkv)*hd, numpy int32) taking the
    canonical fused ``[q|k|v]`` wqkv/bqkv column layout to shard-major:
    ``w[:, perm]`` puts shard s's columns at ``[s*csz, (s+1)*csz)`` as
    ``[q_s|k_s|v_s]`` — exactly the canonical fused layout at the local
    head counts ``nh/mp``/``nkv/mp``. Requires mp | num_kv_heads (and
    mp | num_heads via the GQA rep structure)."""
    nh, nkv, hd = int(num_heads), int(num_kv_heads), int(head_dim)
    if nkv % mp or nh % mp:
        raise ValueError(
            f"mp={mp} must divide num_heads={nh} and num_kv_heads={nkv}")
    dq, dkv = nh * hd, nkv * hd
    q = np.arange(dq, dtype=np.int32).reshape(mp, dq // mp)
    k = dq + np.arange(dkv, dtype=np.int32).reshape(mp, dkv // mp)
    v = dq + dkv + np.arange(dkv, dtype=np.int32).reshape(mp, dkv // mp)
    return np.concatenate([np.concatenate([q[s], k[s], v[s]])
                           for s in range(mp)]).astype(np.int32)


def mp_kv_permutation(num_kv_heads: int, head_dim: int, mp: int):
    """Column permutation (len 2*nkv*hd) taking the pool/scale
    canonical ``[k|v]`` last-dim layout to shard-major
    ``[k_0|v_0|k_1|v_1|...]`` so a plain contiguous mp-split hands
    shard s the canonical ``[k_s|v_s]`` local layout."""
    nkv, hd = int(num_kv_heads), int(head_dim)
    if nkv % mp:
        raise ValueError(f"mp={mp} must divide num_kv_heads={nkv}")
    dkv = nkv * hd
    k = np.arange(dkv, dtype=np.int32).reshape(mp, dkv // mp)
    v = dkv + np.arange(dkv, dtype=np.int32).reshape(mp, dkv // mp)
    return np.concatenate([np.concatenate([k[s], v[s]])
                           for s in range(mp)]).astype(np.int32)


def mp_gather_kv_lastdim(x, mp_axis: str):
    """Inside a shard_map body: all-gather a LOCAL canonical ``[k|v]``
    last dim (2*nkv_loc*hd) back to the FULL canonical ``[k|v]`` layout
    (2*nkv*hd). Pure layout movement — bitwise, no arithmetic."""
    g = jax.lax.all_gather(x, mp_axis, axis=x.ndim - 1, tiled=True)
    m = jax.lax.axis_size(mp_axis)
    loc = g.shape[-1] // (2 * m)
    # tiled gather is shard-major [k0|v0|k1|v1|...]; swap to [k|v]
    parts = g.reshape(g.shape[:-1] + (m, 2, loc))
    return jnp.swapaxes(parts, -3, -2).reshape(g.shape)


def mp_local_kv_lastdim(x, mp_axis: str):
    """Inside a shard_map body: slice this shard's canonical
    ``[k_s|v_s]`` columns out of a FULL canonical ``[k|v]`` last dim —
    the inverse of :func:`mp_gather_kv_lastdim` (replicated-compute
    producers like the chunk forward hand the pool scatter its local
    columns through this)."""
    r = jax.lax.axis_index(mp_axis)
    m = jax.lax.axis_size(mp_axis)
    dkv = x.shape[-1] // 2
    loc = dkv // m
    ax = x.ndim - 1
    k = jax.lax.dynamic_slice_in_dim(x, r * loc, loc, axis=ax)
    v = jax.lax.dynamic_slice_in_dim(x, dkv + r * loc, loc, axis=ax)
    return jnp.concatenate([k, v], axis=-1)


def _mp_gather_cols(act, mp_axis: str):
    """all-gather a column-parallel (b, cols_loc) activation to the full
    (b, cols) operand — shard-contiguous column order, which IS the
    reference order for both the attention output (contiguous q-head
    ranges per shard) and the ffn activation (contiguous column split).
    """
    return jax.lax.all_gather(act, mp_axis, axis=1, tiled=True)


def fused_paged_decode_reference(x, params, kv_pool, block_tables, positions,
                                 cos, sin, *, num_heads: int,
                                 num_kv_heads: int, eps: float = 1e-5,
                                 arch: str = "llama", kv_scales=None,
                                 mp_axis: Optional[str] = None):
    """One decode step against a paged KV pool; pure jnp twin.

    x (b, h); kv_pool (L, NB, BT, 2*nkv*hd); block_tables (b, MB) int32;
    positions (b,) int32 (each slot's append position — the number of
    tokens already cached for that slot); cos/sin (b, hd) fp32 rope rows
    gathered at each slot's position. Returns (x_out (b, h), kv_pool).

    int8 pool mode: kv_scales (L, b, 2*nkv*hd) fp32 — per-SLOT scales
    (serving calibrates each request from its own prefill, unlike the
    batch-shared scales of `fused_decode_reference`).

    The arithmetic is kept line-for-line with `fused_decode_reference`
    (same einsums, same masking, same cast points) so a slot's step is
    bit-identical to the same tokens decoding through a contiguous cache
    — the continuous-batching parity contract (tests/test_serving.py).
    Slots whose block-table tail is unallocated must point spare entries
    at a valid (scratch) block: the copies are masked, not skipped.

    Tensor-parallel mode (``mp_axis`` set, inside a full-manual
    shard_map body): the caller passes the LOCAL head counts, the local
    shard-major wqkv/wg/wu (+ scale/bias) columns and the local pool /
    kv_scales last dim; the per-head attention math above runs
    unchanged over the local heads, and the two column-parallel
    activations (attention output, ffn activation) are all-gathered
    back to full width before the FULL wo/wd matmuls — one collective
    per site, bitwise identical to the mp=1 step (no psum
    re-association). x stays replicated (b, full h) throughout.
    """
    L, NB, BT, dkv2 = kv_pool.shape
    b, MB = block_tables.shape
    S = MB * BT
    dkv = dkv2 // 2
    nh = num_heads
    nkv = num_kv_heads
    hd = dkv // nkv
    rep = nh // nkv
    dq = nh * hd
    dtype = x.dtype
    scale = 1.0 / math.sqrt(hd)
    int8 = "wqkv_s" in params
    gpt = arch == "gpt"
    if arch not in ("llama", "gpt"):
        raise NotImplementedError(
            f"paged decode supports arch llama/gpt, got {arch!r}")
    cos_b = cos.reshape(b, 1, hd).astype(jnp.float32)
    sin_b = sin.reshape(b, 1, hd).astype(jnp.float32)
    rows = jnp.arange(b)
    app_bid = jnp.take_along_axis(
        block_tables, (positions // BT)[:, None], axis=1)[:, 0]   # (b,)
    app_off = positions % BT
    kv_news = []    # per-layer appended rows, written back in ONE scatter

    def wdot(act, key, l):
        w = params[key][l]
        if int8:
            y = jnp.dot(act, w.astype(act.dtype),
                        preferred_element_type=jnp.float32)
            return y * params[f"{key}_s"][l]
        return jnp.dot(act, w, preferred_element_type=jnp.float32)

    xf = x.astype(jnp.float32)
    for l in range(L):
        if gpt:
            xn = _layernorm(xf, params["ln1"][l], params["ln1_b"][l], eps)
        else:
            xn = _rms(xf, params["ln1"][l], eps)
        qkv = wdot(xn, "wqkv", l)
        if gpt:
            qkv = qkv + params["bqkv"][l]
        q = qkv[:, :dq].reshape(b, nh, hd)
        k = qkv[:, dq:dq + nkv * hd].reshape(b, nkv, hd)
        v = qkv[:, dq + nkv * hd:].reshape(b, nkv, hd)
        if not gpt:
            q = _rope1(q, cos_b, sin_b)
            k = _rope1(k, cos_b, sin_b)
        kv_new = jnp.concatenate(
            [k.reshape(b, dkv), v.reshape(b, dkv)], axis=-1)
        if kv_scales is not None:     # int8 pool: per-slot static scales
            kv_new = jnp.clip(
                jnp.round(kv_new.astype(jnp.float32) / kv_scales[l]),
                -127, 127)
        kv_new = kv_new.astype(kv_pool.dtype)
        kv_news.append(kv_new)
        # gather the slot's logical cache view [0, S) for attention
        # (spare table entries gather a scratch block — masked below)
        # and inject this step's append into the GATHERED view; the pool
        # itself is written once after the layer walk (a per-layer
        # `kv_pool.at[l, ...].set` is L pool-sized scatters where the
        # update is not done in place); the values the
        # attention sees are identical either way, because each row's
        # append block is private (copy-on-write invariant) and the
        # injected entry is exactly what the scatter would have stored.
        kvl = kv_pool[l][block_tables].reshape(b, S, dkv2)
        kvl = kvl.at[rows, positions].set(kv_new)
        kl = kvl[:, :, :dkv].astype(jnp.float32)
        vl = kvl[:, :, dkv:].astype(jnp.float32)
        if kv_scales is not None:     # dequantize with per-slot scales
            kl = kl * kv_scales[l][:, None, :dkv]
            vl = vl * kv_scales[l][:, None, dkv:]
        kl = kl.reshape(b, S, nkv, hd)
        vl = vl.reshape(b, S, nkv, hd)
        qg = q.reshape(b, nkv, rep, hd) * scale
        scores = jnp.einsum("bgrd,bsgd->bgrs", qg, kl)
        valid = (jnp.arange(S)[None, None, None]
                 <= positions[:, None, None, None])
        scores = jnp.where(valid, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bgrs,bsgd->bgrd", probs, vl)
        attn = attn.reshape(b, dq).astype(dtype)
        if mp_axis is not None:
            attn = _mp_gather_cols(attn, mp_axis)
        o = wdot(attn, "wo", l)
        if gpt:
            o = o + params["bo"][l]
        xf = xf + o
        if gpt:
            xn2 = _layernorm(xf, params["ln2"][l], params["ln2_b"][l], eps)
            g = wdot(xn2, "wg", l) + params["bg"][l]
            act = jax.nn.gelu(g, approximate=True).astype(dtype)
            if mp_axis is not None:
                act = _mp_gather_cols(act, mp_axis)
            xf = xf + wdot(act, "wd", l) + params["bd"][l]
        else:
            xn2 = _rms(xf, params["ln2"][l], eps)
            g = wdot(xn2, "wg", l)
            u = wdot(xn2, "wu", l)
            act = (jax.nn.silu(g) * u).astype(dtype)
            if mp_axis is not None:
                act = _mp_gather_cols(act, mp_axis)
            xf = xf + wdot(act, "wd", l)
    # ONE combined append for all layers (indices collide for no two
    # rows: append blocks are never shared)
    kv_pool = kv_pool.at[:, app_bid, app_off].set(jnp.stack(kv_news))
    return xf.astype(dtype), kv_pool


def _fused_paged_decode_pallas(x, params, kv_pool, block_tables, positions,
                               *, num_heads: int, num_kv_heads: int,
                               head_dim: int, rope_base: float = 10000.0,
                               eps: float = 1e-5, arch: str = "llama",
                               blocks: Optional[Dict] = None,
                               kv_scales=None, interpret: bool = False):
    """Paged-pool variant of `_fused_decode_pallas` (llama/gpt, no q-split),
    over a tail of K1 tokens a row: K1 == 1 is a decode step, K1 > 1 the
    verify step of speculative decoding. K1 is read off ``x``, which is
    TOKEN-MAJOR flat (K1*b, h): tail token t's rows are the contiguous
    slice [t*b, (t+1)*b), so every per-token stage is a static slice
    (Mosaic cannot stride sublanes) and K1 == 1 is plain (b, h).

    Differences from the contiguous kernel:

    * the KV cache is the (L, NB, BT, 2*nkv*hd) pool; every chunk copy /
      RMW append resolves its physical block through the SMEM block table
      (`bt_ref[r, c]` — the data-dependent DMA addressing the MoE kernel
      pioneered for routed expert weights);
    * `positions` is per-row (a row's append position for tail token 0):
      rope angles, the append RMW offset and the online-softmax limits
      are per row instead of scalar;
    * the walk over cached KV is RAGGED: one loop over the flat list of
      (row, chunk) pairs of `paged_walk`, each row's own blocks and no
      others. A pair's block arrives through a ring of `_WALK_RING`
      block buffers whose prefetch runs ahead ACROSS row boundaries (and
      from one layer's FFN phase into the next layer's walk), and is
      merged into that row's online-softmax state, which lives in VMEM
      scratch indexed by row. A block costs a DMA and two (K1*nh, BT)
      matmuls, so a step's attention time follows the blocks that are
      live; an idle slot (position 0) costs no walk. The scratch holds
      the ring however many slots there are;
    * the tail: one qkv matmul over all K1*b rows; token t's heads are
      staged block-diagonally at q rows [t*nh, (t+1)*nh) of a row's
      (K1*nh, dkv) staging, so one pair of the walk scores ALL tail
      queries (each attends the whole committed prefix). The append
      window [pos//8*8, pos+K1) is NW 8-aligned segments a row, each
      resolved through the block table on its own (BT % 8 == 0: a
      segment never straddles a block; one past the table, a row near its
      cap that over-speculates, is redirected to the scratch block).
      Tail k/v merge at offsets off+t, the window is attended with
      PER-QUERY causal limits (query t masks to pos+t) and written back;
      the o-projection and residual run per tail token. At K1 == 1 the
      window is the one 8-token RMW block of the new token;
    * int8 pool scales are per-SLOT ((L, b, 2*nkv*hd)).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, NB, BT, dkv2 = kv_pool.shape
    b, MB = block_tables.shape
    K1 = x.shape[0] // b
    assert K1 >= 1 and x.shape[0] == K1 * b, (x.shape, b)
    K1b = K1 * b
    NW = (7 + K1 + 7) // 8      # segments of a row's append window
    dkv = dkv2 // 2
    nh = num_heads
    nkv = num_kv_heads
    hd = head_dim
    assert hd == dkv // nkv
    rep = nh // nkv
    h = x.shape[1]
    dq = nh * hd
    dqkv = dq + 2 * dkv
    ffn = params["wg"].shape[2]
    int8 = "wqkv_s" in params
    kvq = kv_scales is not None
    assert kvq == (jnp.dtype(kv_pool.dtype) == jnp.int8), \
        "int8 KV pool needs kv_scales (and vice versa)"
    gpt = arch == "gpt"
    wbytes = 1 if int8 else 2
    cb = jnp.dtype(kv_pool.dtype).itemsize
    ck = BT                 # one block == one KV chunk of the walk
    assert BT % 8 == 0, f"block_tokens {BT} must be a multiple of 8"
    assert dkv % 128 == 0, f"nkv*hd={dkv} must be a lane multiple of 128"
    if blocks is not None:
        assert blocks.get("cache_wbytes", cb) == cb, \
            (f"decode plan assumed a {blocks['cache_wbytes']}-byte KV "
             f"cache but the pool dtype is {kv_pool.dtype} ({cb} B)")
        if blocks.get("q_split", 1) != 1:
            raise ValueError(
                "paged decode does not support the q-split (big-model) "
                "regime yet; build the plan with q_split=1")
        J, fblk = blocks["ffn_blocks"], blocks["fblk"]
        assert ffn == J * fblk, (ffn, blocks)
    else:
        J, fblk = _pick_ffn_blocks(
            ffn, h, fixed_bytes=(dqkv + dq) * h * wbytes, wbytes=wbytes)
    dtype = x.dtype
    scale = 1.0 / math.sqrt(hd)
    ring = _WALK_RING

    def kernel(*refs):
        if gpt:
            (pos_ref, bt_ref, wrow_ref, wchunk_ref, total_ref, posv_ref,
             x_in_ref, ln1_ref, wqkv_ref, wo_ref, ln2_ref, wg_ref,
             wd_ref) = refs[:13]
            wu_ref = None
            i = 13
            (ln1b_ref, ln2b_ref, bqkv_ref, bo_ref, bg_ref,
             bd_ref) = refs[i:i + 6]
            i += 6
        else:
            (pos_ref, bt_ref, wrow_ref, wchunk_ref, total_ref, posv_ref,
             x_in_ref, ln1_ref, wqkv_ref, wo_ref, ln2_ref, wg_ref, wu_ref,
             wd_ref) = refs[:14]
            i = 14
        if int8:
            sqkv_ref, so_ref, sg_ref, su_ref, sd_ref = refs[i:i + 5]
            i += 5
        if kvq:
            kvs_ref = refs[i]          # (b, 2*dkv) per-SLOT cache scales
            i += 1
        kv_in = refs[i]                # aliased with kv_ref
        x_out_ref, kv_ref = refs[i + 1], refs[i + 2]
        (x_s, xn_s, acc_s, q_s, kv32_s, kvwin_s, kvch_s, m_s, l_s, o_s,
         wsem, rsem) = refs[i + 3:]
        del kv_in

        def tail(v, t):
            """v at tail token t; token 0 adds no op to the trace, so that
            K1 == 1 is the decode program and nothing more."""
            return v + t if t else v

        def rows_of(t):
            """Tail token t's rows of the token-major (K1*b, ...) arrays."""
            return slice(t * b, (t + 1) * b)

        def wdot(act, wref, sref):
            w = wref[...]
            if int8:
                y = jnp.dot(act, w.astype(act.dtype),
                            preferred_element_type=jnp.float32)
                return y if sref is None else y * sref[...]
            return jnp.dot(act, w, preferred_element_type=jnp.float32)

        li = pl.program_id(0)
        j = pl.program_id(1)

        # ---- per-row paged DMA descriptors (block table in SMEM) ----
        def seg_pool(l, r, m):
            """Segment m of row r's append window in the pool: 8 tokens
            from pos // 8 * 8 + 8 m, through the block table."""
            p = pos_ref[r]
            if m == 0:      # the open group of 8: always inside the table
                return kv_ref.at[l, bt_ref[r, p // BT],
                                 pl.ds((p % BT) // 8 * 8, 8)]
            q0 = p // 8 * 8 + m * 8
            c = q0 // BT
            # past the table (over-speculation near the cap): to scratch
            bid = jnp.where(c < MB, bt_ref[r, jnp.minimum(c, MB - 1)], 0)
            return kv_ref.at[l, bid, pl.ds(q0 % BT, 8)]

        def seg_read(l, r, m):
            return pltpu.make_async_copy(
                seg_pool(l, r, m), kvwin_s.at[r, pl.ds(m * 8, 8)],
                wsem.at[m * b + r])

        def seg_write(l, r, m):
            return pltpu.make_async_copy(
                kvwin_s.at[r, pl.ds(m * 8, 8)], seg_pool(l, r, m),
                wsem.at[m * b + r])

        def each_seg(do):
            for r in range(b):
                for m in range(NW):
                    do(r, m)

        # ---- the ragged walk: pair t of the flat (row, chunk) list ----
        total = total_ref[0]

        def pair_copy(l, t):
            slot = lax.rem(t, ring)
            return pltpu.make_async_copy(
                kv_ref.at[l, bt_ref[wrow_ref[t], wchunk_ref[t]]],
                kvch_s.at[slot], rsem.at[slot])

        def start_pair(l, t):
            @pl.when(t < total)
            def _():
                pair_copy(l, t).start()

        def start_layer(l):
            """Layer l's window reads and the first pairs of its walk."""
            each_seg(lambda r, m: seg_read(l, r, m).start())
            for t in range(ring - 1):
                start_pair(l, t)

        @pl.when(j == 0)
        def attention_phase():
            posv = posv_ref[...]                       # (b, 1) int32
            blk_v = posv // 8 * 8
            blk3 = blk_v.reshape(b, 1, 1)

            @pl.when(li == 0)
            def _():
                x_s[...] = x_in_ref[...].astype(jnp.float32)
                # one-time zero of the block-diagonal q staging (layers
                # rewrite the same in-block lanes; off-block lanes stay 0)
                q_s[...] = jnp.zeros_like(q_s)
                start_layer(li)

            if gpt:
                xn = _layernorm(x_s[...], ln1_ref[...].reshape(h),
                                ln1b_ref[...].reshape(h), eps)
            else:
                xn = _rms(x_s[...], ln1_ref[...].reshape(h), eps)
            qkv = wdot(xn, wqkv_ref, sqkv_ref if int8 else None)
            if gpt:
                qkv = qkv + bqkv_ref[...]
            else:
                # per-row rope angles from the per-row positions
                half = (lax.broadcasted_iota(jnp.int32, (1, hd), 1)
                        % (hd // 2)).astype(jnp.float32)
                inv_freq = jnp.exp(half * (-2.0 * math.log(rope_base) / hd))

                def rope_at(t):
                    ang = tail(posv, t).astype(jnp.float32) * inv_freq
                    cos_b = jnp.cos(ang)                       # (b, hd)
                    sin_b = jnp.sin(ang)
                    return lambda v: (v * cos_b + jnp.concatenate(
                        [-v[:, hd // 2:], v[:, :hd // 2]], axis=-1) * sin_b)
            # q staged block-diagonally over kv-group lane blocks (see
            # _fused_decode_pallas), token t's heads at q rows [t*nh,
            # (t+1)*nh) with rope at pos + t, an int8 pool's per-slot
            # k-half dequant scales folded in; new k/v staged flat,
            # token-major like x, for the window merge
            for t in range(K1):
                rows = rows_of(t)
                rope2 = (lambda v: v) if gpt else rope_at(t)
                for n in range(nh):
                    g = n // rep
                    qn = rope2(qkv[rows, n * hd:(n + 1) * hd]) * scale
                    if kvq:
                        qn = qn * kvs_ref[...][:, g * hd:(g + 1) * hd]
                    q_s[:, t * nh + n, g * hd:(g + 1) * hd] = qn
                for g in range(nkv):
                    kv32_s[rows, g * hd:(g + 1) * hd] = rope2(
                        qkv[rows, dq + g * hd:dq + (g + 1) * hd])
                    kv32_s[rows, dkv + g * hd:dkv + (g + 1) * hd] = \
                        qkv[rows, dq + dkv + g * hd:dq + dkv + (g + 1) * hd]

            def merge(carry, q, kvblk, live):
                """Online-softmax update of (m, l, acc) with one block of
                keys and values, over ALL heads: for one row and all its
                tail queries (q (K1*nh, dkv), kvblk (w, 2*dkv)) in the
                walk, for every row at once (leading b) and one tail
                query (nh heads) at the append window."""
                m, l, acc = carry
                kf = kvblk[..., :dkv].astype(jnp.float32)
                vf = kvblk[..., dkv:].astype(jnp.float32)
                nb = q.ndim - 2                     # batch dims: 0 or 1
                bd = tuple(range(nb))
                sc = lax.dot_general(
                    q, kf, (((nb + 1,), (nb + 1,)), (bd, bd)),
                    preferred_element_type=jnp.float32)      # (.., heads, w)
                sc = jnp.where(live, sc, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                pp = jnp.exp(sc - m_new)
                acc = acc * alpha + lax.dot_general(
                    pp, vf, (((nb + 1,), (nb,)), (bd, bd)),
                    preferred_element_type=jnp.float32)   # (.., heads, dkv)
                return (m_new,
                        l * alpha + jnp.sum(pp, axis=-1, keepdims=True), acc)

            m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
            l_s[...] = jnp.zeros_like(l_s)
            o_s[...] = jnp.zeros_like(o_s)

            def body(t, carry):
                # the state is in scratch: the loop carries nothing
                start_pair(li, t + ring - 1)
                pair_copy(li, t).wait()
                r = wrow_ref[t]
                idx = wchunk_ref[t] * ck + lax.broadcasted_iota(
                    jnp.int32, (1, ck), 1)
                m_s[r], l_s[r], o_s[r] = merge(
                    (m_s[r], l_s[r], o_s[r]), q_s[r],
                    kvch_s[lax.rem(t, ring)], idx < pos_ref[r] // 8 * 8)
                return carry

            lax.fori_loop(0, total, body, 0)

            # merge each row's tail tokens into its append window at
            # offsets off + t, attend to it from VMEM with query t masked
            # to its own position, write the segments back (waited in FFN
            # j==1)
            each_seg(lambda r, m: seg_read(li, r, m).wait())
            off3 = (posv - blk_v).reshape(b, 1, 1)
            wi = lax.broadcasted_iota(jnp.int32, (1, NW * 8, 1), 1)
            # (where in the window, token t's k/v), staged before the
            # window is loaded: the op order of the one-token program
            places = []
            for t in range(K1):
                sel = wi == tail(off3, t)
                newtok = kv32_s[rows_of(t)]
                if kvq:     # quantize the append with the per-slot scales
                    newtok = jnp.clip(
                        jnp.round(newtok / kvs_ref[...]), -127.0, 127.0)
                places.append((sel, newtok[:, None, :]))
            win = kvwin_s[...].astype(jnp.float32)
            for sel, newtok in places:
                win = jnp.where(sel, newtok, win)
            kvwin_s[...] = win.astype(kv_pool.dtype)
            each_seg(lambda r, m: seg_write(li, r, m).start())
            widx = blk3 + lax.broadcasted_iota(jnp.int32, (1, 1, NW * 8), 2)
            # per tail token, over its static slices of q rows and of x
            # rows: attend to the window, then the o-projection, the
            # residual and the FFN's norm
            for t in range(K1):
                rows = rows_of(t)
                hs = slice(t * nh, (t + 1) * nh)
                _, ls, accs = merge(
                    (m_s[:, hs], l_s[:, hs], o_s[:, hs]), q_s[:, hs],
                    kvwin_s[...],
                    widx < tail(posv.reshape(b, 1, 1), t) + 1)
                norm = accs / ls                            # (b, nh, dkv)
                if kvq:     # per-slot v-half dequant scales, applied once
                    norm = norm * kvs_ref[...][:, dkv:][:, None]
                if rep == 1:
                    bd = (lax.broadcasted_iota(jnp.int32, (1, nh, dkv), 2)
                          // hd == lax.broadcasted_iota(
                              jnp.int32, (1, nh, dkv), 1))
                    attn = jnp.sum(jnp.where(bd, norm, 0.0),
                                   axis=1)                       # (b, dq)
                    oacc = wdot(attn.astype(dtype), wo_ref,
                                so_ref if int8 else None)
                else:
                    oacc = jnp.zeros((b, h), jnp.float32)
                    for g in range(nkv):
                        ng = norm[:, g * rep:(g + 1) * rep,
                                  g * hd:(g + 1) * hd]      # (b, rep, hd)
                        w3 = wo_ref[g * rep * hd:(g + 1) * rep * hd,
                                    :].reshape(rep, hd, h)
                        part = lax.dot_general(
                            ng.astype(dtype),
                            w3.astype(dtype) if int8 else w3,
                            (((2,), (1,)), ((1,), (0,))),
                            preferred_element_type=jnp.float32)  # (rep,b,h)
                        oacc = oacc + jnp.sum(part, axis=0)
                    if int8:
                        oacc = oacc * so_ref[...]
                if gpt:
                    oacc = oacc + bo_ref[...]
                xr = x_s[rows] + oacc
                x_s[rows] = xr
                if gpt:
                    xn_s[rows] = _layernorm(xr, ln2_ref[...].reshape(h),
                                            ln2b_ref[...].reshape(h),
                                            eps).astype(dtype)
                else:
                    xn_s[rows] = _rms(xr, ln2_ref[...].reshape(h),
                                      eps).astype(dtype)
            acc_s[...] = jnp.zeros_like(acc_s)

        @pl.when(j >= 1)
        def ffn_phase():
            @pl.when(j == 1)
            def prefetch_next_layer():
                # drain this layer's window write-backs, then issue the
                # next layer's window reads and the head of its walk
                each_seg(lambda r, m: seg_write(li, r, m).wait())

                @pl.when(li + 1 < L)
                def _():
                    start_layer(li + 1)

            xn = xn_s[...]
            g = wdot(xn, wg_ref, sg_ref if int8 else None)
            if gpt:
                g = g + bg_ref[...]
                act = jax.nn.gelu(g, approximate=True).astype(dtype)
            else:
                u = wdot(xn, wu_ref, su_ref if int8 else None)
                act = (jax.nn.silu(g) * u).astype(dtype)
            acc_s[...] += wdot(act, wd_ref, sd_ref if int8 else None)

            if gpt:
                @pl.when(j == J)
                def _():
                    acc_s[...] += jnp.broadcast_to(bd_ref[...], acc_s.shape)

            @pl.when(j == J)
            def _():
                xr = x_s[...] + acc_s[...]
                x_s[...] = xr
                x_out_ref[...] = xr.astype(dtype)

    def jm(ll, jj):
        # FFN column block: phase j >= 1 streams block j-1; the attention
        # phase keeps the previous layer's last block (no refetch)
        return jnp.where(jj < 1, J - 1, jj - 1)

    def fl(ll, jj):
        return lax.max(ll - (jj < 1), 0)

    grid = (L, 1 + J)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # positions
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # block table
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # work_row
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # work_chunk
        pl.BlockSpec(memory_space=pltpu.SMEM),                 # total
        pl.BlockSpec((b, 1), lambda l, j: (0, 0)),             # posv
        pl.BlockSpec((K1b, h), lambda l, j: (0, 0)),           # x
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),    # ln1
        pl.BlockSpec((None, h, dqkv), lambda l, j: (l, 0, 0)),  # wqkv
        pl.BlockSpec((None, dq, h), lambda l, j: (l, 0, 0)),   # wo
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),    # ln2
        pl.BlockSpec((None, h, fblk),
                     lambda l, j: (fl(l, j), 0, jm(l, j))),     # wg
    ] + ([] if gpt else [
        pl.BlockSpec((None, h, fblk),
                     lambda l, j: (fl(l, j), 0, jm(l, j))),     # wu
    ]) + [
        pl.BlockSpec((None, fblk, h),
                     lambda l, j: (fl(l, j), jm(l, j), 0)),     # wd
    ] + ([
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # ln1_b
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # ln2_b
        pl.BlockSpec((None, 1, dqkv), lambda l, j: (l, 0, 0)),  # bqkv
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # bo
        pl.BlockSpec((None, 1, fblk),
                     lambda l, j: (fl(l, j), 0, jm(l, j))),     # bg
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # bd
    ] if gpt else []) + ([
        pl.BlockSpec((None, 1, dqkv), lambda l, j: (l, 0, 0)),  # sqkv
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # so
        pl.BlockSpec((None, 1, fblk),
                     lambda l, j: (fl(l, j), 0, jm(l, j))),     # sg
        pl.BlockSpec((None, 1, fblk),
                     lambda l, j: (fl(l, j), 0, jm(l, j))),     # su
        pl.BlockSpec((None, 1, h), lambda l, j: (l, 0, 0)),     # sd
    ] if int8 else []) + ([
        pl.BlockSpec((None, b, 2 * dkv), lambda l, j: (l, 0, 0)),  # kvs
    ] if kvq else []) + [
        pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),      # kv pool
    ]
    positions = jnp.asarray(positions, jnp.int32).reshape(b)
    work_row, work_chunk, total = paged_walk(positions, BT, MB)
    operands = [
        positions,
        jnp.asarray(block_tables, jnp.int32),
        work_row, work_chunk, total.astype(jnp.int32).reshape(1),
        positions.reshape(b, 1),
        x,
        params["ln1"][:, None], params["wqkv"], params["wo"],
        params["ln2"][:, None], params["wg"],
        *(() if gpt else (params["wu"],)),
        params["wd"],
        *((params["ln1_b"][:, None], params["ln2_b"][:, None],
           params["bqkv"][:, None], params["bo"][:, None],
           params["bg"][:, None], params["bd"][:, None]) if gpt else ()),
        *((params["wqkv_s"], params["wo_s"], params["wg_s"],
           params["wu_s"], params["wd_s"]) if int8 else ()),
        *((jnp.asarray(kv_scales, jnp.float32),) if kvq else ()),
        kv_pool,
    ]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((K1b, h), lambda l, j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K1b, h), dtype),
            jax.ShapeDtypeStruct(kv_pool.shape, kv_pool.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((K1b, h), jnp.float32),        # x_s
            pltpu.VMEM((K1b, h), dtype),              # xn_s
            pltpu.VMEM((K1b, h), jnp.float32),        # acc_s
            pltpu.VMEM((b, K1 * nh, dkv), jnp.float32),   # q_s (block-diag)
            pltpu.VMEM((K1b, 2 * dkv), jnp.float32),  # kv32_s staging
            pltpu.VMEM((b, NW * 8, 2 * dkv), kv_pool.dtype),  # kvwin_s
            pltpu.VMEM((ring, ck, 2 * dkv), kv_pool.dtype),  # kvch_s ring
            pltpu.VMEM((b, K1 * nh, 1), jnp.float32),     # m_s  } a row's
            pltpu.VMEM((b, K1 * nh, 1), jnp.float32),     # l_s  } online-
            pltpu.VMEM((b, K1 * nh, dkv), jnp.float32),   # o_s  } softmax
            pltpu.SemaphoreType.DMA((NW * b,)),       # wsem (segment, row)
            pltpu.SemaphoreType.DMA((ring,)),         # rsem (per buffer)
        ],
        input_output_aliases={len(in_specs) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes()),
        name=("fused_paged_decode_step" if K1 == 1
              else "fused_paged_verify_step"),
        interpret=interpret,
    )(*operands)
    return out[0], out[1]


def _paged_pallas_interpret(kv_pool, arch: str, blocks: Optional[Dict],
                            mp_axis: Optional[str]) -> Optional[bool]:
    """May the paged Pallas kernel take this call? ``None`` says no (the
    jnp reference runs), otherwise the kernel's ``interpret`` argument.

    The kernel runs on a TPU (or under FLAGS_pallas_interpret elsewhere)
    when the call is not tensor-parallel, the pool's [k|v] halves are lane
    multiples of 128 and a block is whole groups of 8 tokens. A plan made
    for another cache dtype than the pool's is refused, not routed.
    """
    from paddle_tpu.core.flags import flag
    from paddle_tpu.ops import use_pallas
    if arch not in ("llama", "gpt"):
        raise NotImplementedError(
            f"paged decode supports arch llama/gpt, got {arch!r}")
    dkv = kv_pool.shape[-1] // 2
    BT = kv_pool.shape[2]
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    interp = bool(flag("FLAGS_pallas_interpret")) and not use_pallas()
    if mp_axis is not None or not (use_pallas() or interp) \
            or dkv % 128 or BT % 8:
        return None
    cb = jnp.dtype(kv_pool.dtype).itemsize
    if blocks is not None and blocks.get("cache_wbytes", cb) != cb:
        raise ValueError(
            f"decode plan assumed a {blocks['cache_wbytes']}-byte KV "
            f"cache but the pool dtype is {kv_pool.dtype} ({cb} B); "
            f"rebuild the plan with decode_block_plan(cache_wbytes="
            f"{cb})")
    return interp


def fused_paged_decode_step(x, params, kv_pool, block_tables, positions,
                            cos, sin, *, num_heads: int, num_kv_heads: int,
                            eps: float = 1e-5, rope_base: float = 10000.0,
                            arch: str = "llama",
                            blocks: Optional[Dict] = None, kv_scales=None,
                            mp_axis: Optional[str] = None):
    """Dispatch one PAGED decode step: Pallas kernel on TPU (or under
    FLAGS_pallas_interpret), jnp paged reference elsewhere.

    Args follow `fused_paged_decode_reference` (block-table pool, per-row
    positions). cos/sin are the (b, hd) rope rows gathered at each slot's
    position — consumed by the reference path only (the kernel computes
    rope in-kernel from `positions`, like the contiguous kernel).
    `blocks` is a `decode_block_plan` dict; the paged kernel rejects
    q-split plans and consistency-checks `cache_wbytes` against the pool
    dtype. `kv_scales` (L, b, 2*nkv*hd) enables the per-slot int8 pool.
    ``mp_axis`` (inside a shard_map body, local heads/pool columns)
    routes the jnp reference unconditionally — the per-shard problem is
    1/mp of the single-chip one and the collective sits OUTSIDE the
    per-head math, so the XLA path shards cleanly today; teaching the
    Pallas kernel a local-shard mode is a later PR.
    """
    interp = _paged_pallas_interpret(kv_pool, arch, blocks, mp_axis)
    if interp is not None:
        with part("layers"):
            return _fused_paged_decode_pallas(
                x, params, kv_pool, block_tables, positions,
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=kv_pool.shape[-1] // 2 // num_kv_heads,
                rope_base=rope_base, eps=eps, arch=arch, blocks=blocks,
                kv_scales=kv_scales, interpret=interp)
    with part("layers"):
        return fused_paged_decode_reference(
            x, params, kv_pool, block_tables, positions, cos, sin,
            num_heads=num_heads, num_kv_heads=num_kv_heads, eps=eps,
            arch=arch, kv_scales=kv_scales, mp_axis=mp_axis)


# ---------------------------------------------------------------------------
# Coscheduled tick (fused Sarathi): prefill-chunk append + decode step
# ---------------------------------------------------------------------------
#
# The chunked serving tick used to dispatch TWO programs — a chunk
# program (prefill rows) and the fused paged decode (decode rows) —
# with the bf16 KV carry staged between them. Coscheduling folds both
# into ONE program: the chunk rows' freshly computed block-aligned KV
# scatters into the pool on the way into the decode step's chunk walk,
# so the pool crosses exactly one program boundary per tick (one
# donated buffer, one future `shard_map` seam for tensor-parallel
# serving instead of two — ROADMAP "One-program tick").
#
# Pallas-side story: the pool is donated by the caller, so on TPU the
# block scatter lowers to an in-place dynamic-update ahead of the
# kernel's table-resolved KV chunk walk — same HBM buffer, zero copy,
# and the decode walk never reads the chunk rows' blocks (a prefilling
# slot's block-table row points at scratch until adoption), so the
# scheduler may overlap the scatter DMA with the decode kernel's
# weight streaming. On the jnp reference path the win is one pool
# traversal per tick instead of two.


def paged_chunk_scatter(kv_pool, chunk_bids, chunk_kv):
    """Scatter prefill-chunk KV blocks into the paged pool.

    ``chunk_bids`` (n, nb) int32 physical block ids per prefilling row
    (entries past a row's allocated table target the scratch block);
    ``chunk_kv`` (L, n, nb, BT, 2*nkv*hd) the rows' block-aligned KV
    (bf16 chunk appends, or a whole quantized prompt on an int8 last
    chunk). One combined scatter for all layers — the per-layer form
    costs a full pool copy per LAYER on backends without in-place
    scatter (the `fused_paged_decode_reference` lesson)."""
    return kv_pool.at[:, chunk_bids].set(chunk_kv.astype(kv_pool.dtype))


def paged_block_gather(kv_pool, bids):
    """Gather whole physical blocks out of the paged pool — the
    device-side half of a swap-out / prefix export (docs/SERVING.md
    §Hierarchical KV).

    ``bids`` (n,) int32 physical block ids (callers pad to a bucketed
    length with the scratch block, exactly like a block table's
    unallocated tail, so the swap compile set stays finite); returns
    ``(L, n, BT, 2*nkv*hd)`` in the pool dtype. The result is a fresh
    buffer, so the caller may free the source blocks the moment the
    gather is DISPATCHED — the copy is ordered before any later pool
    mutation on the same stream, and ``copy_to_host_async`` overlaps
    the D2H leg with subsequent serving ticks."""
    return kv_pool[:, bids]


def paged_block_scatter(kv_pool, bids, vals):
    """Scatter host-staged block payloads back into the paged pool —
    the device-side half of a swap-in / tier-prefix promotion. Same
    contract as :func:`paged_chunk_scatter` (donate the pool at the jit
    boundary; entries past the real count target scratch); split out so
    swap traffic shares one seam with chunk appends instead of growing
    a second scatter idiom. The fused tick program never sees these
    blocks mid-flight: they land in the pool BEFORE the dispatch that
    first reads them, so compile-set and donation pins are untouched."""
    return kv_pool.at[:, bids].set(vals.astype(kv_pool.dtype))


def fused_paged_tick_step(x, params, kv_pool, block_tables, positions,
                          cos, sin, *, num_heads: int, num_kv_heads: int,
                          eps: float = 1e-5, rope_base: float = 10000.0,
                          arch: str = "llama",
                          blocks: Optional[Dict] = None, kv_scales=None,
                          chunk_bids=None, chunk_kv=None,
                          mp_axis: Optional[str] = None):
    """One fused Sarathi tick: coschedule a prefill-chunk append with
    the fused paged decode step — ONE program, the pool threaded
    through both updates (donate it at the jit boundary; the serving
    engine pins the aliasing via ``analysis.runtime.donation_report``).

    ``chunk_bids``/``chunk_kv`` (see :func:`paged_chunk_scatter`) may
    be ``None``, in which case this is exactly
    :func:`fused_paged_decode_step` — chunkless ticks share the body.
    The chunk rows' blocks and the decode rows' append blocks are
    disjoint by construction (prefilling slots idle against scratch
    until adoption), so the scatter/decode order is value-irrelevant;
    scatter-first matches the two-program tick it replaces.

    Under ``mp_axis`` the chunk forward runs REPLICATED (the full-model
    prefill math), so ``chunk_kv`` arrives in the FULL canonical [k|v]
    layout; each shard slices its own canonical columns out before the
    scatter into its local pool shard."""
    if chunk_bids is not None:
        if mp_axis is not None \
                and chunk_kv.shape[-1] != kv_pool.shape[-1]:
            chunk_kv = mp_local_kv_lastdim(chunk_kv, mp_axis)
        with part("attn"):
            kv_pool = paged_chunk_scatter(kv_pool, chunk_bids, chunk_kv)
    return fused_paged_decode_step(
        x, params, kv_pool, block_tables, positions, cos, sin,
        num_heads=num_heads, num_kv_heads=num_kv_heads, eps=eps,
        rope_base=rope_base, arch=arch, blocks=blocks,
        kv_scales=kv_scales, mp_axis=mp_axis)


# ---------------------------------------------------------------------------
# Paged verify (speculative decoding): score a k-token tail per slot
# ---------------------------------------------------------------------------
#
# Speculative decoding turns k proposed tokens per slot into ONE scoring
# dispatch instead of k serial decode dispatches: the verify pass runs
# the whole stack over the tail [t0, p1..pk] (t0 = the slot's last
# sampled token, p* the proposals), appends every tail token's KV, and
# returns the k+1 hidden states the engine samples the target tokens
# from. On the TPU it is the paged decode kernel, given the tail
# (`_fused_paged_decode_pallas`: K1 = k+1); there is no second kernel.
# Decode is bandwidth-bound, so weights streamed once per k+1 tokens
# instead of once per token is the whole win (ROADMAP "Speculative
# decoding on the paged engine").
#
# Rejected-token KV is handled by POSITION, not by rollback: a slot's
# attention always masks to its own append position, and future appends
# overwrite stale entries in place — accepting a tokens is just
# "advance the position by a+1".


def fused_paged_verify_reference(x, params, kv_pool, block_tables,
                                 positions, cos, sin, *, num_heads: int,
                                 num_kv_heads: int, eps: float = 1e-5,
                                 arch: str = "llama", kv_scales=None,
                                 mp_axis: Optional[str] = None):
    """Score a K1-token tail per slot against the paged pool; pure jnp.

    x (b, K1, h): the embedded tail tokens — x[:, j] is token j embedded
    at position ``positions + j``; cos/sin (b, K1, hd) are the matching
    rope rows. kv_pool/block_tables/positions as in
    `fused_paged_decode_reference` (``positions`` is each slot's append
    position for tail token 0). Returns (x_out (b, K1, h), kv_pool) with
    every tail token's KV appended at positions [pos, pos+K1).

    Bit-identity contract (the speculative-vs-sequential parity pin,
    tests/test_serving_spec.py): tail token j's computation is the SAME
    per-token math as `fused_paged_decode_reference` — one (b, h) row
    per step, same einsums, same masks, same cast points — run K1 times
    over per-layer gathered views that carry each token's append
    forward (injection produces the exact values a scatter-then-regather
    would). A verify pass over an all-accepted tail therefore produces
    bitwise the logits K1 sequential decode steps would.

    Appends whose position falls outside the slot's table range (the
    over-speculation tail of a slot near its cap) are redirected to the
    scratch block (block 0) — garbage by contract, never attended (a
    query's mask never reaches past its own position).

    ``mp_axis`` arms the same tensor-parallel contract as
    `fused_paged_decode_reference`: local heads/pool columns in, one
    all_gather per column-parallel activation, bitwise mp=1 logits out.
    """
    L, NB, BT, dkv2 = kv_pool.shape
    b, MB = block_tables.shape
    K1 = x.shape[1]
    S = MB * BT
    dkv = dkv2 // 2
    nh = num_heads
    nkv = num_kv_heads
    hd = dkv // nkv
    rep = nh // nkv
    dq = nh * hd
    dtype = x.dtype
    scale = 1.0 / math.sqrt(hd)
    int8 = "wqkv_s" in params
    gpt = arch == "gpt"
    if arch not in ("llama", "gpt"):
        raise NotImplementedError(
            f"paged verify supports arch llama/gpt, got {arch!r}")
    rows = jnp.arange(b)

    def wdot(act, key, l):
        w = params[key][l]
        if int8:
            y = jnp.dot(act, w.astype(act.dtype),
                        preferred_element_type=jnp.float32)
            return y * params[f"{key}_s"][l]
        return jnp.dot(act, w, preferred_element_type=jnp.float32)

    # per-layer gathered views, carried across the tail tokens so token
    # j+1 sees token j's append without a per-token pool scatter
    # (one combined scatter at the end, like the decode reference)
    views = [kv_pool[l][block_tables].reshape(b, S, dkv2)
             for l in range(L)]
    app_news = []                   # per-token (L, b, dkv2) appends
    outs = []
    for j in range(K1):
        posj = positions + j
        cos_b = cos[:, j].reshape(b, 1, hd).astype(jnp.float32)
        sin_b = sin[:, j].reshape(b, 1, hd).astype(jnp.float32)
        xf = x[:, j].astype(jnp.float32)
        kv_news = []
        for l in range(L):
            if gpt:
                xn = _layernorm(xf, params["ln1"][l], params["ln1_b"][l],
                                eps)
            else:
                xn = _rms(xf, params["ln1"][l], eps)
            qkv = wdot(xn, "wqkv", l)
            if gpt:
                qkv = qkv + params["bqkv"][l]
            q = qkv[:, :dq].reshape(b, nh, hd)
            k = qkv[:, dq:dq + nkv * hd].reshape(b, nkv, hd)
            v = qkv[:, dq + nkv * hd:].reshape(b, nkv, hd)
            if not gpt:
                q = _rope1(q, cos_b, sin_b)
                k = _rope1(k, cos_b, sin_b)
            kv_new = jnp.concatenate(
                [k.reshape(b, dkv), v.reshape(b, dkv)], axis=-1)
            if kv_scales is not None:   # int8 pool: per-slot scales
                kv_new = jnp.clip(
                    jnp.round(kv_new.astype(jnp.float32) / kv_scales[l]),
                    -127, 127)
            kv_new = kv_new.astype(kv_pool.dtype)
            kv_news.append(kv_new)
            # inject this token's append into the carried view; an
            # out-of-range position (over-speculation past the cap) is
            # dropped — its pool write goes to scratch below
            kvl = views[l].at[rows, posj].set(kv_new, mode="drop")
            views[l] = kvl
            kl = kvl[:, :, :dkv].astype(jnp.float32)
            vl = kvl[:, :, dkv:].astype(jnp.float32)
            if kv_scales is not None:
                kl = kl * kv_scales[l][:, None, :dkv]
                vl = vl * kv_scales[l][:, None, dkv:]
            kl = kl.reshape(b, S, nkv, hd)
            vl = vl.reshape(b, S, nkv, hd)
            qg = q.reshape(b, nkv, rep, hd) * scale
            scores = jnp.einsum("bgrd,bsgd->bgrs", qg, kl)
            valid = (jnp.arange(S)[None, None, None]
                     <= posj[:, None, None, None])
            scores = jnp.where(valid, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bgrs,bsgd->bgrd", probs, vl)
            attn = attn.reshape(b, dq).astype(dtype)
            if mp_axis is not None:
                attn = _mp_gather_cols(attn, mp_axis)
            o = wdot(attn, "wo", l)
            if gpt:
                o = o + params["bo"][l]
            xf = xf + o
            if gpt:
                xn2 = _layernorm(xf, params["ln2"][l], params["ln2_b"][l],
                                 eps)
                g = wdot(xn2, "wg", l) + params["bg"][l]
                act = jax.nn.gelu(g, approximate=True).astype(dtype)
                if mp_axis is not None:
                    act = _mp_gather_cols(act, mp_axis)
                xf = xf + wdot(act, "wd", l) + params["bd"][l]
            else:
                xn2 = _rms(xf, params["ln2"][l], eps)
                g = wdot(xn2, "wg", l)
                u = wdot(xn2, "wu", l)
                act = (jax.nn.silu(g) * u).astype(dtype)
                if mp_axis is not None:
                    act = _mp_gather_cols(act, mp_axis)
                xf = xf + wdot(act, "wd", l)
        outs.append(xf.astype(dtype))
        app_news.append(jnp.stack(kv_news))         # (L, b, dkv2)
    # ONE combined scatter of every (layer, token) append; positions
    # past the table range land in the scratch block
    posm = positions[:, None] + jnp.arange(K1)[None]        # (b, K1)
    cm = posm // BT
    bid = jnp.take_along_axis(block_tables,
                              jnp.minimum(cm, MB - 1), axis=1)
    bid = jnp.where(cm < MB, bid, 0)                # 0 = scratch block
    off = posm % BT
    vals = jnp.stack(app_news, axis=2)              # (L, b, K1, dkv2)
    kv_pool = kv_pool.at[:, bid, off].set(vals)
    return jnp.stack(outs, axis=1), kv_pool


def fused_paged_verify_step(x, params, kv_pool, block_tables, positions,
                            cos, sin, *, num_heads: int, num_kv_heads: int,
                            eps: float = 1e-5, rope_base: float = 10000.0,
                            arch: str = "llama",
                            blocks: Optional[Dict] = None, kv_scales=None,
                            mp_axis: Optional[str] = None):
    """Dispatch one PAGED verify step (speculative decoding's scoring
    pass): the paged Pallas kernel with a tail of K1 tokens on TPU (or
    under FLAGS_pallas_interpret), jnp verify reference elsewhere.

    x (b, K1, h) — the K1 tail tokens (the slot's last sampled token
    followed by its K proposals) embedded at positions ``positions + j``;
    cos/sin (b, K1, hd) the matching rope rows (reference path only —
    the kernel computes rope in-kernel from `positions`). Returns
    (x_out (b, K1, h), kv_pool) with every tail token's KV appended.
    The engine samples the target tokens from x_out and commits the
    longest proposal prefix that matches its own stream's samples —
    docs/SERVING.md §Speculative decoding.
    """
    b, K1, h = x.shape
    interp = _paged_pallas_interpret(kv_pool, arch, blocks, mp_axis)
    if interp is not None:
        with part("layers"):
            # token-major flat: token j's rows contiguous at [j*b,
            # (j+1)*b) so the kernel's per-token stages are static
            # slices
            xf = x.transpose(1, 0, 2).reshape(K1 * b, h)
            y, pool = _fused_paged_decode_pallas(
                xf, params, kv_pool, block_tables, positions,
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=kv_pool.shape[-1] // 2 // num_kv_heads,
                rope_base=rope_base, eps=eps, arch=arch, blocks=blocks,
                kv_scales=kv_scales, interpret=interp)
            return y.reshape(K1, b, h).transpose(1, 0, 2), pool
    with part("layers"):
        return fused_paged_verify_reference(
            x, params, kv_pool, block_tables, positions, cos, sin,
            num_heads=num_heads, num_kv_heads=num_kv_heads, eps=eps,
            arch=arch, kv_scales=kv_scales, mp_axis=mp_axis)
