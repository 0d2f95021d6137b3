"""Operations and bytes the grouped prefill kernel of arch ``xing4``
needs (``moe_grouped_ffn_prefill``), from the published sizes alone.

One call is one expert layer of one wave prefill. Every expert is taken
as touched: a wave routes ``top_k`` picks a position over near-uniform
experts, and at 8 picks an expert or more the chance that one gets none
is under 1e-3 a layer (1e-5 at the 16 a 256-token bucket gives). A
trained, skewed router would need the count from the program.
"""

MIN_PICKS_AN_EXPERT = 8


def every_expert_touched(d: dict, routed_rows: float) -> bool:
    """Whether ``routed_rows`` picks a call are enough to take all of
    ``d["experts"]`` as touched."""
    return routed_rows >= MIN_PICKS_AN_EXPERT * d["experts"]


def moe_prefill_calls(d: dict, calls: int, routed_rows: int,
                      weight_bytes: int = 2, elem_bytes: int = 2) -> dict:
    """``calls`` calls that push ``routed_rows`` rows (positions x
    ``top_k``, summed over the calls) through the experts: the three
    matrices of all ``experts`` read once a call, every routed row read
    and its result written once, and 3 matrices x 2 flops a weight a
    routed row."""
    h, f = d["h"], d["expert_ffn"]
    return {
        "bytes": (calls * d["experts"] * 3 * h * f * weight_bytes
                  + routed_rows * 2 * h * elem_bytes),
        "flops": routed_rows * 3 * 2 * h * f,
    }
