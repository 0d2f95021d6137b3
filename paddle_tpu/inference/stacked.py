"""Stacked-weight Llama inference engine — 7B-class serving on one chip.

Reference: the fused_multi_transformer serving stack
(paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu +
fused_multi_transformer_int8, SURVEY.md §2.2 fusion + §2.4 inference) is
how the reference serves 7B-class checkpoints: one weight image in the
fused kernel's layout, consumed by both context (prefill) and decode.

The nn.Layer `generate()` path stacks per-layer weights into the fused
kernel's (L, ...) layout *inside* the jitted program, so both copies are
live at the stack boundary — fine at 1B, impossible for Llama-2-7B int8
(2 × 6.6 GiB) on a 16 GiB v5e. This engine owns ONE stacked copy:

* prefill is a `lax.scan` over the layer dim reading the same stacks the
  decode kernel streams (the standard TPU big-model shape — scan over
  layers, static shapes, weights dequantized per layer inside the scan);
* decode rides `ops.fused_decode` with the `decode_block_plan` that also
  sized the stacks (qkv column split + padded FFN blocks at 7B scale);
* `from_config` materializes random int8/bf16 weights host-side straight
  into the stacked layout (benchmarking; never two copies), and
  `from_state_dict` imports a per-layer checkpoint state layer by layer.
"""

import logging
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.ops import fused_decode as fd
from paddle_tpu.ops.rope import rope_cos_sin

__all__ = ["StackedLlamaDecoder"]

logger = logging.getLogger("paddle_tpu.inference")


class StackedLlamaDecoder:
    """Inference-only Llama with parameters in the fused kernel's stacked
    layout. `params` follows `build_fused_params` naming ({ln1, wqkv, wo,
    ln2, wg, wu, wd} (+ `*_s` int8 scales)); `embed_w` (vocab, h) bf16;
    `head` either ("tied",), ("dense", w) or ("int8", q, scale)."""

    def __init__(self, cfg, params: Dict[str, jax.Array], embed_w, norm_w,
                 head, blocks: Optional[Dict] = None):
        self.cfg = cfg
        self.params = params
        self.embed_w = embed_w
        self.norm_w = norm_w
        self.head = head
        int8 = "wqkv_s" in params
        hd = cfg.head_dim
        dq = cfg.num_heads * hd
        self.blocks = blocks or fd.decode_block_plan(
            cfg.hidden_size, dq + 2 * cfg.kv_heads * hd, dq, hd,
            cfg.intermediate_size, wbytes=1 if int8 else 2)
        self._jit_cache = {}

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_config(cls, cfg, *, int8: bool = True, seed: int = 0,
                    dtype=jnp.bfloat16):
        """Random weights, materialized ON DEVICE directly in the stacked
        layout via jax.random (no host->device transfer of 7B weights)
        and never held twice."""
        # tpu-lint: allow(rng-stream): weight-init stream, not request
        # sampling — request draws fold per-request seeds (PR 5)
        key = jax.random.PRNGKey(seed)
        L, h, ffn = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
        hd = cfg.head_dim
        dq, dkv = cfg.num_heads * hd, cfg.kv_heads * hd
        dqkv = dq + 2 * dkv
        blocks = fd.decode_block_plan(h, dqkv, dq, hd, ffn,
                                      wbytes=1 if int8 else 2)
        fp = blocks["ffn_pad"]
        sd = cfg.initializer_range

        def nxt():
            nonlocal key
            # tpu-lint: allow(rng-stream): weight-init stream fork
            key, sub = jax.random.split(key)
            return sub

        def w(*shape, pad_axis=None, pad_to=0):
            if int8:
                # tpu-lint: allow(rng-stream): weight-init draw
                a = jax.random.randint(nxt(), shape, -127, 128,
                                       dtype=jnp.int8)
            else:
                # tpu-lint: allow(rng-stream): weight-init draw
                a = (jax.random.normal(nxt(), shape, jnp.float32)
                     * sd).astype(dtype)
            if pad_axis is not None and pad_to > shape[pad_axis]:
                widths = [(0, 0)] * a.ndim
                widths[pad_axis] = (0, pad_to - shape[pad_axis])
                a = jnp.pad(a, widths)
            return a

        def sc(n, pad_to=0):
            a = jnp.full((L, 1, n), sd / 127.0, jnp.float32)
            if pad_to > n:
                a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_to - n)),
                            constant_values=1.0)
            return a

        params = {
            "ln1": jnp.ones((L, h), dtype),
            "ln2": jnp.ones((L, h), dtype),
            "wqkv": w(L, h, dqkv),
            "wo": w(L, dq, h),
            "wg": w(L, h, ffn, pad_axis=2, pad_to=fp),
            "wu": w(L, h, ffn, pad_axis=2, pad_to=fp),
            "wd": w(L, ffn, h, pad_axis=1, pad_to=fp),
        }
        if int8:
            params.update(wqkv_s=sc(dqkv), wo_s=sc(h), wg_s=sc(ffn, fp),
                          wu_s=sc(ffn, fp), wd_s=sc(h))
        # tpu-lint: allow(rng-stream): weight-init draw
        embed_w = (jax.random.normal(nxt(), (cfg.vocab_size, h),
                                     jnp.float32) * sd).astype(dtype)
        norm_w = jnp.ones((h,), dtype)
        if cfg.tie_word_embeddings:
            head = ("tied",)
        elif int8:
            # tpu-lint: allow(rng-stream): weight-init draw
            head = ("int8",
                    jax.random.randint(nxt(), (h, cfg.vocab_size), -127,
                                       128, dtype=jnp.int8),
                    jnp.full((cfg.vocab_size,), sd / 127.0, jnp.float32))
        else:
            # tpu-lint: allow(rng-stream): weight-init draw
            head = ("dense",
                    (jax.random.normal(nxt(), (h, cfg.vocab_size),
                                       jnp.float32) * sd).astype(dtype))
        return cls(cfg, params, embed_w, norm_w, head, blocks)

    @classmethod
    def from_state_dict(cls, cfg, state: Dict[str, jax.Array]):
        """Import a per-layer LlamaForCausalLM state dict (bf16 or
        weight-only-int8 — paddle_tpu.quantization naming)."""
        int8 = "model.layers.0.self_attn.q_proj.weight_q" in state
        hd = cfg.head_dim
        dq = cfg.num_heads * hd
        blocks = fd.decode_block_plan(
            cfg.hidden_size, dq + 2 * cfg.kv_heads * hd, dq, hd,
            cfg.intermediate_size, wbytes=1 if int8 else 2)
        params = fd.build_fused_params(state, cfg.num_layers,
                                       ffn_pad=blocks["ffn_pad"])
        if cfg.tie_word_embeddings:
            head = ("tied",)
        elif int8 and "lm_head.weight_q" in state:
            head = ("int8", state["lm_head.weight_q"],
                    state["lm_head.weight_scale"])
        else:
            head = ("dense", state["lm_head.weight"])
        return cls(cfg, params, state["model.embed_tokens.weight"],
                   state["model.norm.weight"], head, blocks)

    # -- forward pieces ----------------------------------------------------

    def _head_logits(self, xn, embed_w=None, head_arrays=None):
        """head_arrays/embed_w default to self.* for eager use; the jitted
        generate passes them as traced args (baking the ~400 MB 7B
        embed+lm_head into the executable as constants would hold a second
        on-device copy)."""
        kind = self.head[0]
        if kind == "tied":
            from paddle_tpu.ops import tied_unembed
            ew = self.embed_w if embed_w is None else embed_w
            return tied_unembed(xn, ew)
        ha = tuple(self.head[1:]) if head_arrays is None else head_arrays
        if kind == "int8":
            q, s = ha
            y = jnp.dot(xn, q.astype(xn.dtype),
                        preferred_element_type=jnp.float32)
            return y * s
        return jnp.dot(xn, ha[0])

    def _final_norm(self, x, norm_w=None):
        w = self.norm_w if norm_w is None else norm_w
        return _rms_np(x, w, self.cfg.rms_norm_eps, w.dtype)

    def prefill(self, params, ids, total: int, cache_dtype=jnp.bfloat16,
                embed_w=None):
        """Full-prompt forward as a lax.scan over the layer dim. Returns
        (last-position hidden (b, h) fp32, kv cache (L, b, total, 2*dkv))."""
        cfg = self.cfg
        b, s = ids.shape
        h, hd = cfg.hidden_size, cfg.head_dim
        nh, nkv = cfg.num_heads, cfg.kv_heads
        rep = nh // nkv
        dq, dkv = nh * hd, nkv * hd
        eps = cfg.rms_norm_eps
        int8 = "wqkv_s" in params
        dtype = self.embed_w.dtype
        scale = 1.0 / math.sqrt(hd)
        cos, sin = rope_cos_sin(s, hd, base=cfg.rope_base)
        cos = cos[None, :, None, :].astype(jnp.float32)
        sin = sin[None, :, None, :].astype(jnp.float32)

        def rope(t):                       # (b, s, n, hd)
            half = t.shape[-1] // 2
            rot = jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)
            return t * cos + rot * sin

        def mm(act, wl, sl):
            y = jnp.dot(act, wl.astype(act.dtype),
                        preferred_element_type=jnp.float32)
            return y * sl if sl is not None else y

        causal = jnp.tril(jnp.ones((s, s), bool))

        def layer(xf, wl):
            xn = _rms_np(xf, wl["ln1"], eps, dtype)
            qkv = mm(xn, wl["wqkv"], wl.get("wqkv_s"))
            q = rope(qkv[..., :dq].reshape(b, s, nh, hd))
            k = rope(qkv[..., dq:dq + dkv].reshape(b, s, nkv, hd))
            v = qkv[..., dq + dkv:].reshape(b, s, nkv, hd)
            qg = q.reshape(b, s, nkv, rep, hd) * scale
            sc_ = jnp.einsum("bsgrd,btgd->bgrst", qg, k)
            sc_ = jnp.where(causal[None, None, None], sc_, fd.NEG_INF)
            pr = jax.nn.softmax(sc_, axis=-1)
            at = jnp.einsum("bgrst,btgd->bsgrd", pr, v)
            o = mm(at.reshape(b, s, dq).astype(dtype), wl["wo"],
                   wl.get("wo_s"))
            xf = xf + o
            xn2 = _rms_np(xf, wl["ln2"], eps, dtype)
            g = mm(xn2, wl["wg"], wl.get("wg_s"))
            u = mm(xn2, wl["wu"], wl.get("wu_s"))
            act = (jax.nn.silu(g) * u).astype(dtype)
            xf = xf + mm(act, wl["wd"], wl.get("wd_s"))
            kflat = jnp.concatenate(
                [k.reshape(b, s, dkv), v.reshape(b, s, dkv)],
                axis=-1).astype(cache_dtype)
            return xf, kflat

        x = jnp.take(self.embed_w if embed_w is None else embed_w, ids,
                     axis=0).astype(jnp.float32)
        keys = [k for k in ("ln1", "wqkv", "wqkv_s", "wo", "wo_s", "ln2",
                            "wg", "wg_s", "wu", "wu_s", "wd", "wd_s")
                if k in params]
        stacks = {k: params[k] for k in keys}
        x, kv = lax.scan(lambda c, wl: layer(c, wl), x, stacks)
        kv = jnp.pad(kv, ((0, 0), (0, 0), (0, total - s), (0, 0)))
        return x[:, -1], kv

    # -- generation --------------------------------------------------------

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 cache_dtype=jnp.bfloat16,
                 deadline_s: Optional[float] = None, request_seeds=None,
                 _kv_chunk: int = 0):
        """Prefill + fused-kernel decode, the whole loop one jitted scan.
        Returns (b, prompt+new) ids including the prompt.

        cache_dtype=jnp.int8 decodes against an int8 KV cache: prefill
        runs bf16 (the calibration pass), the cache is quantized with
        per-(layer, kv-head) scales (ops.fused_decode.quantize_kv_cache)
        and the fused kernel streams int8 KV chunks — halving the
        per-step cache DMA, the long-context (s >= 2048) decode regime
        where cache bytes dominate the roofline.

        Resilience (see inference.generate): ``deadline_s`` runs the
        request as chunked decode programs and returns early at the
        budget; accelerator OOM retries ONCE with a halved KV chunk
        (``resilience.decode_degraded{stage=halved_chunk}``) — this
        engine has no layered fallback (the stacked weights ARE the
        fused layout), so a second OOM propagates.

        Sampling rides per-request RNG streams (see inference.generate):
        row r draws token t from fold_in(PRNGKey(request_seeds[r]), t),
        default seeds ``seed + r`` — batch-composition-invariant."""
        from paddle_tpu import observability as obs
        from paddle_tpu.inference import (_fold_rows, _request_seeds,
                                          _row_keys, _sample_logits)

        input_ids = jnp.asarray(input_ids)
        b, prompt_len = input_ids.shape
        total = -(-(prompt_len + max_new_tokens) // 128) * 128
        cfg = self.cfg
        kv_int8 = jnp.dtype(cache_dtype) == jnp.int8
        if not kv_int8 and jnp.dtype(cache_dtype).itemsize != 2:
            raise ValueError(
                "StackedLlamaDecoder decodes against a bf16 or int8 KV "
                f"cache; got cache_dtype={jnp.dtype(cache_dtype).name}")
        seeds0 = _request_seeds(request_seeds, seed, b)
        tracer = obs.active_tracer()
        if tracer is None and deadline_s is not None:
            # deadline checks happen at chunk boundaries — ride the split
            # programs under a local, un-attached tracer
            tracer = obs.Tracer()
        jk = (b, prompt_len, max_new_tokens, float(temperature), int(top_k),
              float(top_p), jnp.dtype(cache_dtype).name, int(_kv_chunk))
        run = self._jit_cache.get(jk)
        traced_fns = self._jit_cache.get(jk + ("traced",))
        if (run is None if tracer is None else traced_fns is None):
            cos_tab, sin_tab = rope_cos_sin(total, cfg.head_dim,
                                            base=cfg.rope_base)
            blocks = (dict(self.blocks, cache_wbytes=1) if kv_int8
                      else self.blocks)

            def logits(x, embed_w, norm_w, head_arrays):
                return self._head_logits(
                    self._final_norm(x, norm_w), embed_w, head_arrays)

            def _prefill_impl(params, embed_w, norm_w, head_arrays, ids,
                              seeds):
                with jax.named_scope("decode.prefill"):
                    x, kv = self.prefill(
                        params, ids, total,
                        jnp.bfloat16 if kv_int8 else cache_dtype,
                        embed_w=embed_w)
                if kv_int8:
                    with jax.named_scope("decode.cache_quantize"):
                        kv, kv_scales = fd.quantize_kv_cache(kv,
                                                             cfg.kv_heads)
                else:
                    kv_scales = None
                keys = _row_keys(seeds)
                with jax.named_scope("decode.sample"):
                    tok = _sample_logits(
                        logits(x, embed_w, norm_w, head_arrays),
                        _fold_rows(keys, 0), temperature, top_k, top_p)
                return (tok, kv, keys), kv_scales

            def _decode_impl(params, embed_w, norm_w, head_arrays, carry,
                             kv_scales, i0, nsteps):
                def step(carry, i):
                    tok, kv, keys = carry
                    ki = _fold_rows(keys, i)
                    pos = prompt_len + i - 1
                    x = jnp.take(embed_w, tok, axis=0)
                    cos = lax.dynamic_slice_in_dim(cos_tab, pos, 1, axis=0)
                    sin = lax.dynamic_slice_in_dim(sin_tab, pos, 1, axis=0)
                    x, kv = fd.fused_decode_step(
                        x, params, kv, pos, cos, sin,
                        num_heads=cfg.num_heads, num_kv_heads=cfg.kv_heads,
                        eps=cfg.rms_norm_eps, rope_base=cfg.rope_base,
                        blocks=blocks, kv_scales=kv_scales,
                        kv_chunk=_kv_chunk)
                    with jax.named_scope("decode.sample"):
                        nxt = _sample_logits(
                            logits(x, embed_w, norm_w, head_arrays), ki,
                            temperature, top_k, top_p)
                    return (nxt, kv, keys), nxt

                return lax.scan(step, carry, i0 + jnp.arange(nsteps))

            if tracer is None:
                def run_impl(params, embed_w, norm_w, head_arrays, ids,
                             key):
                    carry, kv_scales = _prefill_impl(
                        params, embed_w, norm_w, head_arrays, ids, key)
                    tok = carry[0]
                    carry, toks = _decode_impl(
                        params, embed_w, norm_w, head_arrays, carry,
                        kv_scales, 1, max_new_tokens - 1)
                    return jnp.concatenate([tok[:, None], toks.T], axis=1)

                run = jax.jit(run_impl)
                self._jit_cache[jk] = run
            else:
                # donate the KV carry across chunk dispatches (see
                # inference.carry_donate_argnums: avoids a full-cache
                # copy per chunk)
                from paddle_tpu.inference import carry_donate_argnums
                traced_fns = (
                    jax.jit(_prefill_impl),
                    jax.jit(_decode_impl, static_argnums=(7,),
                            donate_argnums=carry_donate_argnums(4)))
                self._jit_cache[jk + ("traced",)] = traced_fns

        head_arrays = tuple(self.head[1:])
        from paddle_tpu.resilience import faults as _faults
        from paddle_tpu.resilience import (is_resource_exhausted,
                                           record_event,
                                           remaining_deadline)

        import time as _time
        t_request = _time.perf_counter()
        try:
            _faults.maybe_fire("decode.dispatch")
            if tracer is None:
                new = run(self.params, self.embed_w, self.norm_w,
                          head_arrays, input_ids, seeds0)
            else:
                dkv = cfg.kv_heads * cfg.head_dim
                itemsize = 1 if kv_int8 else jnp.dtype(cache_dtype).itemsize
                kv_cache_bytes = (cfg.num_layers * b * total * 2 * dkv
                                  * itemsize)
                avg_len = min(prompt_len + max_new_tokens / 2.0, total)
                pf, dc = traced_fns
                pieces = obs.run_traced_decode(
                    tracer,
                    lambda: pf(self.params, self.embed_w, self.norm_w,
                               head_arrays, input_ids, seeds0),
                    lambda carry, aux, i0, c: dc(
                        self.params, self.embed_w, self.norm_w, head_arrays,
                        carry, aux, i0, c),
                    batch=b, max_new_tokens=max_new_tokens,
                    deadline_s=deadline_s,
                    attrs=dict(
                        arch="llama-stacked", fused=True,
                        prompt_len=prompt_len,
                        kv_cache_dtype=jnp.dtype(cache_dtype).name,
                        kv_cache_bytes=int(kv_cache_bytes),
                        kv_bytes_per_step=int(kv_cache_bytes * avg_len
                                              / total)))
                new = jnp.concatenate(pieces, axis=1)
        except Exception as e:  # noqa: BLE001 — filtered by class below
            if not (is_resource_exhausted(e) and _kv_chunk == 0):
                raise
            record_event("decode_degraded", stage="halved_chunk")
            logger.warning(
                "stacked decode OOM (%s); retrying with a reduced KV chunk",
                e)
            # retry rungs inherit the REMAINING request budget; 32 sits
            # strictly below every auto-picked chunk (64/128), so the
            # retry is never a recompile of the config that just OOM'd
            remaining = remaining_deadline(deadline_s, t_request)
            return self.generate(
                input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed, cache_dtype=cache_dtype, deadline_s=remaining,
                request_seeds=request_seeds, _kv_chunk=32)
        return jnp.concatenate([input_ids, new], axis=1)

    def num_params(self):
        """True (unpadded) parameter count — roofline accounting."""
        cfg = self.cfg
        h, ffn, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
        dq, dkv = cfg.num_heads * hd, cfg.kv_heads * hd
        per_layer = 2 * h + h * (dq + 2 * dkv) + dq * h + 3 * h * ffn
        n = cfg.vocab_size * h + cfg.num_layers * per_layer + h
        if not cfg.tie_word_embeddings:
            n += h * cfg.vocab_size
        return n


def _rms_np(x, w, eps, dtype):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(dtype) * w
