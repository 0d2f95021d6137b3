"""Layer ``kernels``: device time of the Mosaic kernels against their
roofline. Time comes from the trace, operations and bytes from shapes
(``harness/opcount.py``), peaks from ``harness/peaks.py``."""

from harness import opcount, serve, trace_reduce


def paged_decode_roofline(obs):
    """``fused_paged_decode_step``: every block's weights, the cached
    keys and values of the active slots and one token's writes per slot,
    over the kernel's device time in the traced part."""
    red = obs.get("trace")
    if red is None:
        return None
    sec, calls = trace_reduce.name_seconds(red, "fused_paged_decode_step")
    lo, hi = obs["trace_steps"]
    if not calls or hi <= lo:
        return None
    rows, tokens = serve.attended_tokens(obs["all_requests"], lo, hi)
    d = opcount.dims(obs["config"])
    per = opcount.paged_decode_step(d, rows / (hi - lo), tokens / (hi - lo))
    r = opcount.roofline(per["flops"] * calls, per["bytes"] * calls, sec,
                         obs["peaks"])
    return dict(value=100.0 * r["share"], bound=r["bound"], calls=calls,
                mean_call_ms=1e3 * sec / calls, mean_rows=rows / (hi - lo),
                mean_cached_tokens=tokens / (hi - lo))


def flash_roofline(obs):
    """``flash_attention_fwd``, ``_bwd_dq`` and ``_bwd_dkv`` together:
    the flops exact causal attention needs forward and backward, over the
    three kernels' device time in the traced part."""
    red = obs.get("trace")
    if red is None:
        return None
    sec, _ = trace_reduce.name_seconds(red, "flash_attention")
    _, n_fwd = trace_reduce.name_seconds(red, "flash_attention_fwd")
    _, n_bwd = trace_reduce.name_seconds(red, "flash_attention_bwd_dkv")
    if not n_fwd or not n_bwd:
        return None
    d = opcount.dims(obs["config"])
    per = opcount.flash_attention(obs["batch"], d["heads"], obs["seq"],
                                  d["head_dim"])
    r = opcount.roofline(
        n_fwd * per["fwd_flops"] + n_bwd * per["bwd_flops"],
        n_fwd * per["fwd_bytes"] + n_bwd * per["bwd_bytes"], sec,
        obs["peaks"])
    return dict(value=100.0 * r["share"], bound=r["bound"],
                fwd_calls=n_fwd, bwd_calls=n_bwd)
