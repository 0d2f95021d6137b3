"""Operations and bytes the algorithm needs, from shapes alone.

These are the numerators of every roofline share and of MFU. They count
what the mathematics requires (no recomputation, no padding rows, no
inactive batch rows), so a kernel that does extra work scores lower, as
it should. All sizes come from the configuration file's published keys.
"""

import importlib


def dims(cfg: dict) -> dict:
    """The sizes the counts need (h, layers, heads, kv_heads, head_dim,
    ffn, ffn_mats, vocab, tied, positions), read from the configuration
    file's published keys by the architecture's own
    ``reference_<arch>.dims``: a new architecture brings its own."""
    try:
        ref = importlib.import_module(f"{__package__}.reference_{cfg['arch']}")
    except ImportError:
        raise ValueError(
            f"no operation counts for arch {cfg['arch']!r}: there is no "
            f"harness/reference_{cfg['arch']}.py") from None
    return ref.dims(cfg)


def layer_matmul_params(d: dict) -> int:
    """Weights of one block's matrix multiplications: q, k, v and output
    projections and the feed-forward matrices."""
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return d["h"] * (q + 2 * kv) + q * d["h"] + d["ffn_mats"] * d["h"] * d["ffn"]


def matmul_params(d: dict) -> int:
    """Every weight a token is multiplied by: the blocks and the output
    head (vocab x h, tied or not). Embedding look-ups are not matmuls."""
    return d["layers"] * layer_matmul_params(d) + d["vocab"] * d["h"]


def param_count(d: dict) -> int:
    """All parameters: blocks, embeddings (positions included), head if
    untied. Norm scales and biases are left out (under 0.1 %)."""
    n = d["layers"] * layer_matmul_params(d) + d["vocab"] * d["h"]
    n += d["positions"] * d["h"]
    if not d["tied"]:
        n += d["vocab"] * d["h"]
    return n


def kv_bytes_per_token(d: dict, elem_bytes: int = 2) -> int:
    """Keys and values one token keeps, over all layers."""
    return d["layers"] * 2 * d["kv_heads"] * d["head_dim"] * elem_bytes


def paged_decode_step(d: dict, rows: int, attended_tokens: int,
                      weight_bytes: int = 2, kv_elem_bytes: int = 2) -> dict:
    """One call of the fused paged decode kernel: every block's weights
    streamed once, the keys and values of ``attended_tokens`` cached
    tokens (summed over the ``rows`` active slots) read, one token's keys
    and values written per row. The embedding, final norm and output
    head run outside the kernel and are not counted."""
    lw = d["layers"] * layer_matmul_params(d)
    kvb = kv_bytes_per_token(d, kv_elem_bytes)
    q = d["heads"] * d["head_dim"]
    return {
        "bytes": lw * weight_bytes + (attended_tokens + rows) * kvb,
        # 2 flops per weight per row; scores and weighted values are
        # 2 * q flops each per attended token per layer
        "flops": 2 * lw * rows + 4 * q * d["layers"] * attended_tokens,
    }


def flash_attention(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True) -> dict:
    """Forward and backward of exact attention as the flash algorithm
    needs them: forward scores and weighted values (2 matmuls); backward
    scores again (they are never stored), dP, dV, dK and dQ (5 matmuls).
    A causal mask halves each. Bytes: q, k, v, o read or written once
    forward; those, do and the three gradients backward (bf16)."""
    mm = 2 * batch * heads * seq * seq * head_dim
    if causal:
        mm //= 2
    tensor = batch * heads * seq * head_dim * 2
    return {"fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
            "fwd_bytes": 4 * tensor, "bwd_bytes": 8 * tensor}


def train_flops_per_token(d: dict, seq: int) -> int:
    """Forward and backward of one token: 6 per matmul weight (output
    head included, embedding look-ups not) plus attention's 12 * L * h * s
    halved for the causal mask. No recomputation is counted."""
    q = d["heads"] * d["head_dim"]
    return 6 * matmul_params(d) + 6 * d["layers"] * q * seq


def roofline(flops: float, nbytes: float, seconds: float, peaks: dict) -> dict:
    """Share of the roofline reached: the least time the chip could take
    (the larger of flops over peak flops and bytes over peak bandwidth)
    over the time taken, and which of the two bounds holds."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"share": max(t_flops, t_bytes) / seconds,
            "bound": "flops" if t_flops > t_bytes else "hbm"}
