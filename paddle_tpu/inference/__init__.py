"""Inference: jitted KV-cache decoding + Predictor veneer.

Reference (SURVEY.md §2.4-inference, §2.2-fusion): AnalysisPredictor loads a
saved program and runs IR-optimized inference; generation-time decode rides
the fused_multi_transformer / masked_multihead_attention CUDA kernels.

TPU-native: the whole decode step (all layers, cache update, sampling) is
ONE jitted program — XLA fuses what fused_multi_transformer hand-fuses;
there is no separate "optimized program" artifact because jit compilation
IS the optimization pass.
"""

import logging
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.nn.layer import functional_call

logger = logging.getLogger("paddle_tpu.inference")


def _inference_state(model):
    """ALL named parameters, not just trainable ones — a quantized model's
    int8 weights are trainable=False and must still be bound (otherwise
    jit bakes them into the program as constants)."""
    return model.state_dict(include_buffers=False)


def _greedy_argmax(logits):
    """Two-stage argmax over the vocab dim. XLA lowers a flat argmax over
    ~50K lanes to an iota+reduce running at ~11 GB/s (0.15 ms/step in the
    r5 decode profile); reducing lane-blocks first then the tiny block
    axis is ~50x faster. First-occurrence tie-breaking matches
    jnp.argmax: the first block holding the global max wins, then the
    first lane within it."""
    v = logits.shape[-1]
    if v % 128 or v < 4096:
        return jnp.argmax(logits, axis=-1)
    lb = logits.reshape(logits.shape[:-1] + (v // 128, 128))
    bmax = jnp.max(lb, axis=-1)
    bidx = jnp.argmax(lb, axis=-1).astype(jnp.int32)     # (b, v/128)
    blk = jnp.argmax(bmax, axis=-1).astype(jnp.int32)    # (b,)
    lane = jnp.take_along_axis(bidx, blk[..., None], axis=-1)[..., 0]
    return blk * 128 + lane


def _filter_logits(logits, top_k=0, top_p=1.0):
    """Apply top-k / nucleus (top-p) filtering to (b, vocab) fp32 logits.

    The top-p cutoff is RANK-based: the kept set is exactly the smallest
    prefix of the (stable) descending sort whose cumulative probability
    reaches top_p. A value-based cutoff (`logits < cutoff`) would retain
    every logit EQUAL to the boundary value, overshooting the nucleus
    whenever duplicates straddle it (pinned by tests/test_serving.py)."""
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        order = jnp.argsort(-logits, axis=-1)        # stable: ties keep
        sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep rank i iff the mass BEFORE it is < top_p — the smallest
        # prefix with cum >= top_p; rank 0 is kept unconditionally (its
        # prior mass is 0, but `0.0 < 0.0` is False at top_p == 0.0 and
        # an all-masked row would sample token id 0); scatter the rank
        # mask back to vocab order
        keep_sorted = ((cum - probs) < top_p).at[..., 0].set(True)
        inv = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def _sample_logits(logits, key, temperature=1.0, top_k=0, top_p=1.0):
    """logits (b, vocab) → token ids (b,). Greedy when temperature == 0.

    ``key`` is either one PRNG key — a shared gumbel stream over the
    batch — or a (b, 2) batch of per-ROW keys (the per-request streams
    `generate` builds from ``request_seeds``), sampled row-by-row so a
    request's tokens don't depend on its batch neighbours."""
    if temperature == 0.0:
        return _greedy_argmax(logits)
    logits = _filter_logits(logits.astype(jnp.float32) / temperature,
                            top_k, top_p)
    if key.ndim > 1:                 # per-request streams
        return jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg))(key, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _row_keys(seeds):
    """(b,) request seeds → (b, 2) per-row base PRNG keys."""
    # tpu-lint: allow(rng-stream): THE sanctioned base-key builder —
    # every request-serving draw folds a token index into these keys
    return jax.vmap(jax.random.PRNGKey)(seeds)


def _fold_rows(keys, t):
    """Fold token index t into each row's base key: the key that samples
    token t of every request, whatever batch it currently rides in."""
    return jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, t)


def carry_donate_argnums(*argnums):
    """``donate_argnums`` for a chunked-decode KV carry, on every
    backend: the chunk program returns the carry it was given, so the
    compiled module aliases it (``analysis.runtime.donation_report``
    pins that). ONE definition shared by `generate`'s traced chunk
    programs and the stacked decoder's — and a spelling the ``donation``
    lint rule reads argnums through (docs/ANALYSIS.md §donation)."""
    return tuple(argnums)


def resident_carry_donate_argnums(*argnums):
    """``donate_argnums`` for a RESIDENT fixed-shape carry — the
    serving engine's fused-tick buffers (the paged KV pool, the
    chunked-prefill KV carry, the ngram history). A resident carry is
    RMW'd in place (``dynamic_update_slice`` at a static cursor; input
    shape == output shape), the caller rebinds it from the program
    output every tick, and the compiled module's ``input_output_alias``
    table records the aliasing — ``analysis.runtime.donation_report``
    pins it (tests/test_analysis.py), and the ``donation`` lint rule
    reads argnums through this spelling like any ``*_donate_argnums``
    helper."""
    return tuple(argnums)


def _request_seeds(request_seeds, seed, b):
    """(b,) uint32 per-request seeds — explicit streams, or the default
    ``seed + row`` convention. ONE definition: `generate`, the stacked
    decoder and the serving engine must agree on the default or the
    engine-vs-isolated sampling parity contract silently breaks."""
    s = (jnp.asarray(request_seeds, jnp.uint32)
         if request_seeds is not None
         else jnp.uint32(seed) + jnp.arange(b, dtype=jnp.uint32))
    assert s.shape == (b,), f"request_seeds must be ({b},), got {s.shape}"
    return s


def generate(model, input_ids, max_new_tokens=32, temperature=0.0, top_k=0,
             top_p=1.0, eos_token_id: Optional[int] = None, seed: int = 0,
             state: Optional[Dict] = None, cache_dtype=jnp.bfloat16,
             deadline_s: Optional[float] = None,
             request_seeds=None, return_lengths: bool = False,
             _kv_chunk: int = 0, _force_layered: bool = False):
    """Autoregressive generation with a preallocated KV cache.

    model must expose forward(ids, cache=..., start_pos=...) and
    init_cache(batch, max_len) (LlamaForCausalLM-style). Returns
    (b, prompt+new) token ids including the prompt.

    The whole decode loop runs as ONE jitted lax.scan (a single device
    dispatch — the fused_multi_transformer-style decode path); after an eos
    every subsequent token of that row is emitted as eos.

    cache_dtype=jnp.int8 enables the int8 KV-cache decode mode (the
    fused_multi_transformer_int8 cache_kv quant analog): prefill runs in
    bf16 and acts as the calibration pass, the stacked cache is quantized
    with per-(layer, kv-head) scales, and every decode step streams int8
    KV + dequantizes on the compute path. Requires the fused decode plan
    (llama, gpt and moe archs).

    Resilience (paddle_tpu.resilience; docs/RESILIENCE.md):

    * ``deadline_s`` — per-request wall-clock budget. The request runs
      as a prefill + chunked-decode program pair (the traced-decode
      machinery) so the deadline is checked at chunk boundaries; on
      expiry the tokens produced so far come back (≥ 1) and
      ``resilience.deadline_exceeded`` increments. ``None`` (default)
      keeps the single-dispatch program untouched.
    * Accelerator OOM (RESOURCE_EXHAUSTED) triggers the degradation
      ladder: retry with a HALVED KV chunk (less VMEM scratch), then
      fall back to the layered (non-fused) decode path; each rung
      increments ``resilience.decode_degraded{stage=...}``. An int8
      cache stops at the halved-chunk rung (the layered path cannot
      stream a quantized cache — and a bf16 refill would only grow the
      footprint that just OOM'd). ``_kv_chunk``/``_force_layered`` are
      the ladder's internal knobs, not API.

    With no fault plan armed and no deadline, the request takes the
    exact code path it always did — bit-identical tokens, no added
    dispatches (pinned by tests/test_resilience.py).

    Sampling uses PER-REQUEST RNG streams: row r draws token t from
    ``fold_in(PRNGKey(request_seeds[r]), t)`` (default seeds
    ``seed + r``), so a request's sampled tokens are invariant to its
    batch composition — the property the continuous-batching engine
    (paddle_tpu.serving) needs for join/leave parity with isolated
    calls. ``return_lengths=True`` additionally returns the per-row
    generated length (tokens before the first eos) as an int32 numpy
    array — slot-free accounting for serving, pad-waste accounting for
    decode_bench — as ``(ids, lengths)``.
    """
    from paddle_tpu.core.flags import flag

    input_ids = jnp.asarray(input_ids)
    b, prompt_len = input_ids.shape
    total = prompt_len + max_new_tokens
    state = state if state is not None else _inference_state(model)
    kv_int8 = jnp.dtype(cache_dtype) == jnp.int8
    # fused decode path (ops.fused_decode, the fused_multi_transformer
    # analog): whole decoder stack per step in one Pallas call on TPU /
    # one stacked jnp program elsewhere. The cache length is padded to the
    # kernel's 128-token chunk size (attention masks the tail either way).
    plan = (model.fused_decode_plan(state, probe=True)
            if flag("FLAGS_fused_decode") and not _force_layered
            and hasattr(model, "fused_decode_plan") else None)
    if plan is not None and b > plan.get("max_batch", b):
        plan = None     # e.g. MoE no-drop bound b ≤ per-expert capacity
    if plan is not None and "cache_lanes" in plan:
        # a plan for the paged engine alone (its own step body over its
        # own pool rows): a contiguous cache rides the layered path
        plan = None
    if plan is not None and not kv_int8 \
            and jnp.dtype(cache_dtype).itemsize != 2:
        # the fused kernel's cache layouts are 2-byte (bf16) or int8; an
        # fp32 cache would trip the kernel's cache_wbytes contract check
        # on a kernel-eligible config — ride the layered path instead
        plan = None
    if kv_int8 and plan is None:
        raise ValueError(
            "cache_dtype=int8 requires the fused decode path (an eligible "
            "fused_decode_plan); this model/config cannot ride it")
    if plan is not None:
        total = -(-total // 128) * 128
    # int8 mode prefills through the layered path in bf16 (the
    # calibration pass); the cache is quantized after stacking.
    # The cache is created INSIDE the prefill program (matching the
    # serving engine's wave prefill): an eager jnp.zeros here would
    # compile a per-shape zeros program and upload its fill scalar on
    # every call — the exact per-request H2D the dispatch sanitizer
    # (paddle_tpu.analysis.runtime) guards against.
    cache_init_dtype = jnp.bfloat16 if kv_int8 else cache_dtype
    eos = -1 if eos_token_id is None else int(eos_token_id)

    # One decode program per static configuration, cached on the model so
    # repeated generate() calls with the same shapes don't retrace. The KV
    # cache is not donated: the program returns only tokens, so there is no
    # output buffer to alias — XLA frees the cache after its last in-scan
    # use regardless.
    #
    # Telemetry (paddle_tpu.observability): with NO tracer attached the
    # whole request stays the single-dispatch `run` program below — the
    # only added cost is the `active_tracer()` read. With a tracer
    # attached, the SAME prefill/decode impls are compiled as a prefill
    # program + a chunked decode program, so TTFT and per-chunk TPOT are
    # real host-observed measurements; tokens are identical (same step
    # function, split scan).
    from paddle_tpu import observability as obs

    tracer = obs.active_tracer()
    if tracer is None and deadline_s is not None:
        # a deadline needs chunk boundaries to check the clock at: ride
        # the traced split programs (token-identical to the single
        # dispatch) under a local, un-attached tracer
        tracer = obs.Tracer()
    jit_cache = model.__dict__.setdefault("_generate_jit_cache", {})
    jit_key = (b, prompt_len, max_new_tokens, float(temperature),
               int(top_k), float(top_p), eos, jnp.dtype(cache_dtype).name,
               model.training, plan is not None, int(_kv_chunk))
    run = jit_cache.get(jit_key)
    traced_fns = jit_cache.get(jit_key + ("traced",))
    if (run is None if tracer is None else traced_fns is None):
        if plan is not None:
            from paddle_tpu.ops import rope as rope_ops
            from paddle_tpu.ops.fused_decode import (fused_decode_step,
                                                     quantize_kv_cache)

            cos_tab, sin_tab = rope_ops.rope_cos_sin(
                total, plan["head_dim"], base=plan["rope_base"])

            def _prefill_impl(state, ids, seeds):
                # rebuild the plan from the traced state so the stacked
                # weights flow from the `state` argument (not constants)
                plan_t = model.fused_decode_plan(state)
                cache = model.init_cache(b, total, dtype=cache_init_dtype)
                # prefill on the layered path, then stack for the kernel
                with jax.named_scope("decode.prefill"):
                    out, cache = functional_call(model, state, ids,
                                                 cache=cache, start_pos=0)
                    # fused cache layout: combined flat (L, b, S, 2*nkv*hd)
                    kv = jnp.stack([jnp.concatenate(
                        [c["k"].reshape(b, total, -1),
                         c["v"].reshape(b, total, -1)],
                        axis=-1) for c in cache])
                if kv_int8:     # prefill was the calibration pass
                    with jax.named_scope("decode.cache_quantize"):
                        kv, kv_scales = quantize_kv_cache(
                            kv, plan_t["num_kv_heads"])
                else:
                    kv_scales = None
                keys = _row_keys(seeds)
                with jax.named_scope("decode.sample"):
                    tok = _sample_logits(out[:, -1, :], _fold_rows(keys, 0),
                                         temperature, top_k, top_p)
                finished = jnp.zeros((b,), bool)
                return (tok, kv, keys, finished), kv_scales

            def _decode_impl(state, carry, kv_scales, i0, nsteps):
                plan_t = model.fused_decode_plan(state)
                blocks = plan_t.get("blocks")
                if kv_int8 and blocks is not None:
                    blocks = dict(blocks, cache_wbytes=1)

                def step(carry, i):
                    tok, kv, keys, finished = carry
                    finished = finished | (tok == eos)
                    ki = _fold_rows(keys, i)
                    pos = prompt_len + i - 1
                    x = plan_t["embed"](tok, pos)
                    cos = lax.dynamic_slice_in_dim(cos_tab, pos, 1, axis=0)
                    sin = lax.dynamic_slice_in_dim(sin_tab, pos, 1, axis=0)
                    x, kv = fused_decode_step(
                        x, plan_t["params"], kv, pos, cos, sin,
                        num_heads=plan_t["num_heads"],
                        num_kv_heads=plan_t["num_kv_heads"],
                        eps=plan_t["eps"], rope_base=plan_t["rope_base"],
                        arch=plan_t.get("arch", "llama"),
                        top_k=plan_t.get("top_k", 2),
                        blocks=blocks, kv_scales=kv_scales,
                        kv_chunk=_kv_chunk)
                    with jax.named_scope("decode.sample"):
                        nxt = _sample_logits(plan_t["head"](x), ki,
                                             temperature, top_k, top_p)
                    nxt = jnp.where(finished, jnp.full_like(nxt, eos), nxt)
                    return (nxt, kv, keys, finished), nxt

                return lax.scan(step, carry, i0 + jnp.arange(nsteps))
        else:
            def _prefill_impl(state, ids, seeds):
                cache = model.init_cache(b, total, dtype=cache_init_dtype)
                with jax.named_scope("decode.prefill"):
                    out, cache = functional_call(model, state, ids,
                                                 cache=cache, start_pos=0)
                keys = _row_keys(seeds)
                with jax.named_scope("decode.sample"):
                    tok = _sample_logits(out[:, -1, :], _fold_rows(keys, 0),
                                         temperature, top_k, top_p)
                finished = jnp.zeros((b,), bool)
                return (tok, cache, keys, finished), None

            def _decode_impl(state, carry, _aux, i0, nsteps):
                def step(carry, i):
                    tok, cache, keys, finished = carry
                    finished = finished | (tok == eos)
                    ki = _fold_rows(keys, i)
                    out, cache = functional_call(
                        model, state, tok[:, None], cache=cache,
                        start_pos=prompt_len + i - 1)
                    with jax.named_scope("decode.sample"):
                        nxt = _sample_logits(out[:, -1, :], ki, temperature,
                                             top_k, top_p)
                    nxt = jnp.where(finished, jnp.full_like(nxt, eos), nxt)
                    return (nxt, cache, keys, finished), nxt

                return lax.scan(step, carry, i0 + jnp.arange(nsteps))

        if tracer is None:
            def run_impl(state, ids, seeds):
                carry, aux = _prefill_impl(state, ids, seeds)
                tok = carry[0]
                carry, toks = _decode_impl(state, carry, aux, 1,
                                           max_new_tokens - 1)
                return jnp.concatenate([tok[:, None], toks.T], axis=1)

            run = jax.jit(run_impl)
            jit_cache[jit_key] = run
        else:
            # donate the carry across the chunk dispatches so XLA
            # aliases the KV buffer instead of copying it per chunk (a 7B
            # cache copied every 32 tokens would skew the TPOT this mode
            # measures and double peak HBM)
            traced_fns = (
                jax.jit(_prefill_impl),
                jax.jit(_decode_impl, static_argnums=(4,),
                        donate_argnums=carry_donate_argnums(1)))
            jit_cache[jit_key + ("traced",)] = traced_fns

    # per-request RNG streams: row r samples token t from
    # fold_in(PRNGKey(seeds0[r]), t) — batch-composition-invariant
    seeds0 = _request_seeds(request_seeds, seed, b)
    from paddle_tpu.resilience import faults as _faults
    from paddle_tpu.resilience import (is_resource_exhausted, record_event,
                                       remaining_deadline)

    import time as _time
    t_request = _time.perf_counter()
    try:
        # injectable accelerator-OOM site (one global read when disarmed)
        _faults.maybe_fire("decode.dispatch")
        if tracer is None:
            new_tokens = run(state, input_ids, seeds0)
        else:
            # analytic cache accounting for the request span: total
            # allocated KV bytes at the cache dtype, and the avg bytes a
            # decode step streams (cache fill averaged over the window).
            # eval_shape: the cache lives only inside the programs now,
            # so size it abstractly (no allocation, no transfer)
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(b, total,
                                         dtype=cache_init_dtype))
            leaves = jax.tree_util.tree_leaves(cache_shapes)
            itemsize = 1 if kv_int8 else jnp.dtype(cache_dtype).itemsize
            kv_cache_bytes = int(sum(l.size * itemsize for l in leaves))
            avg_len = min(prompt_len + max_new_tokens / 2.0, total)
            pf, dc = traced_fns
            pieces = obs.run_traced_decode(
                tracer,
                lambda: pf(state, input_ids, seeds0),
                lambda carry, aux, i0, c: dc(state, carry, aux, i0, c),
                batch=b, max_new_tokens=max_new_tokens,
                deadline_s=deadline_s,
                attrs=dict(
                    arch=(plan.get("arch", "llama") if plan is not None
                          else type(model).__name__),
                    fused=plan is not None, prompt_len=prompt_len,
                    kv_cache_dtype=jnp.dtype(cache_dtype).name,
                    kv_cache_bytes=kv_cache_bytes,
                    kv_bytes_per_step=int(kv_cache_bytes * avg_len / total)))
            new_tokens = jnp.concatenate(pieces, axis=1)
    except Exception as e:  # noqa: BLE001 — ladder filters by class below
        if not is_resource_exhausted(e):
            raise
        remaining = remaining_deadline(deadline_s, t_request)
        retry_kw = dict(max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        eos_token_id=eos_token_id, seed=seed, state=state,
                        cache_dtype=cache_dtype, deadline_s=remaining,
                        request_seeds=request_seeds,
                        return_lengths=return_lengths)
        if plan is not None and _kv_chunk == 0:
            record_event("decode_degraded", stage="halved_chunk")
            logger.warning(
                "decode OOM (%s); retrying with a reduced KV chunk", e)
            # 32 is strictly below every auto-picked chunk (64 in the 7B
            # q-split regime, 128 plain, 256 MoE-int8), so the rung is
            # never a no-op recompile of the configuration that just
            # OOM'd; it always divides the 128-padded cache length
            return generate(model, input_ids, _kv_chunk=32, **retry_kw)
        if plan is not None and not kv_int8:
            record_event("decode_degraded", stage="layered")
            logger.warning(
                "decode OOM persists (%s); falling back to the layered "
                "(non-fused) decode path", e)
            return generate(model, input_ids, _force_layered=True,
                            **retry_kw)
        raise
    if eos_token_id is not None:
        # tpu-lint: allow(host-sync): once-per-request D2H — the eos
        # trim + gen_len accounting need the tokens on host anyway
        arr = np.asarray(new_tokens)
        # per-row generated length: tokens before the first eos
        hit = arr == eos_token_id
        gen_len = np.where(hit.any(axis=1), hit.argmax(axis=1),
                           arr.shape[1]).astype(np.int32)
        # trim columns where every row is already past its eos
        done = np.cumsum(hit, axis=1) > 1
        keep = int((~done.all(axis=0)).sum())
        new_tokens = new_tokens[:, :max(keep, 1)]
    else:
        # no host pull: keep the default path's async dispatch (shapes
        # are static, so gen_len needs no device sync)
        gen_len = np.full(new_tokens.shape[0], new_tokens.shape[1],
                          np.int32)
    out = jnp.concatenate([input_ids, new_tokens], axis=1)
    return (out, gen_len) if return_lengths else out


class Predictor:
    """AnalysisPredictor parity: load a saved model + config, run jitted
    batched forward."""

    def __init__(self, model, state: Optional[Dict] = None):
        self.model = model
        self.state = state if state is not None else _inference_state(model)
        self._fwd = jax.jit(
            lambda st, *args, **kw: functional_call(model, st, *args, **kw))

    @classmethod
    def from_checkpoint(cls, model, path):
        from paddle_tpu.framework.io import load
        sd = load(path)
        model.set_state_dict(sd)
        return cls(model)

    def run(self, *args, **kwargs):
        return self._fwd(self.state, *args, **kwargs)

    __call__ = run

    def generate(self, input_ids, **kwargs):
        return generate(self.model, input_ids, state=self.state, **kwargs)
