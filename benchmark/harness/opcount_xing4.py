"""Operations and bytes the two decode kernels of arch ``xing4`` need,
from the published sizes (``reference_xing4.dims``) alone.

As in ``opcount.py`` these are what the mathematics requires: a cached
token is ``kv_lora_rank + qk_rope_head_dim`` values (576), whatever
padding the pool's rows carry; an expert's weights count once a step if
at least one row chose it and not at all otherwise; idle batch rows
count nothing.
"""


def latent_bytes_per_token(d: dict, elem_bytes: int = 2) -> int:
    """What one token keeps in the cache, over all layers."""
    return d["layers"] * d["cache_lanes"] * elem_bytes


def mla_decode_call(d: dict, rows: float, attended_tokens: float,
                    elem_bytes: int = 2) -> dict:
    """ONE call of ``mla_paged_decode`` (one layer of one step):
    ``attended_tokens`` cached rows read (summed over the ``rows`` active
    slots), one row written and attended per slot, the absorbed queries
    in (heads x 576) and the latent-space outputs out (heads x d_c,
    float32). A score is a dot over 576 values, the weighted sum runs
    over d_c: 2 * (576 + d_c) flops a head a token."""
    lanes, heads, d_c = d["cache_lanes"], d["heads"], d["d_c"]
    seen = attended_tokens + rows          # each row also sees its new token
    return {
        "bytes": (seen * lanes * elem_bytes
                  + rows * heads * (lanes * elem_bytes + d_c * 4)),
        "flops": 2 * heads * (lanes + d_c) * seen,
    }


def moe_ffn_call(d: dict, rows: float, touched: float,
                 weight_bytes: int = 2, elem_bytes: int = 2) -> dict:
    """ONE call of ``moe_grouped_ffn_decode`` (one expert layer of one
    step): the three matrices of each of the ``touched`` experts read
    once, the rows' activations in and out and their routing weights;
    each of the ``rows`` active slots runs ``top_k`` experts' SwiGLU
    (3 matrices, 2 flops a weight)."""
    h, f, k = d["h"], d["expert_ffn"], d["top_k"]
    return {
        "bytes": (touched * 3 * h * f * weight_bytes
                  + rows * (2 * h * elem_bytes + d["experts"] * 4)),
        "flops": rows * k * 3 * 2 * h * f,
    }


def step_weight_bytes(d: dict, touched: float, weight_bytes: int = 2) -> int:
    """Every weight one decode step streams: attention, mixers, dense
    and shared FFNs, routers, the touched experts of every expert layer
    (``touched`` a layer) and the output head."""
    h, heads = d["h"], d["heads"]
    attn = (h * d["d_q"] + d["d_q"] * heads * (d["d_n"] + d["d_r"])
            + h * d["cache_lanes"] + d["d_c"] * heads * (d["d_n"] + d["d_v"])
            + heads * d["d_v"] * h)
    n = d["streams"]
    mixers = 2 * n * h * (2 * n + n * n)
    dense = 3 * h * d["ffn"]
    expert = 3 * h * d["expert_ffn"]
    moe = expert * (d["shared"] + touched) + h * d["experts"]
    return weight_bytes * (d["layers"] * (attn + mixers)
                           + d["dense_layers"] * dense
                           + d["moe_layers"] * moe + d["vocab"] * h)
