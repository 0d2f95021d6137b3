"""Operations and bytes the prefill's expanded latent attention needs
(``mla_flash_prefill``), from the published sizes alone.

One call is one layer of one wave prefill: ``rows`` requests of ``s_pad``
padded positions each, behind ``R`` cached ones. The flops are the
causal triangle's, exactly: query ``i`` sees ``R + i + 1`` keys, so a
kernel that skips whole blocks behind the causal edge but computes the
blocks on the diagonal in full can never read over 100 %. The bytes are
every operand once: the queries, the expanded keys and values of all
``R + s_pad`` positions, the one rotary key the heads share, the output.
"""


def mla_prefill_call(d: dict, s_pad: int, R: int = 0, rows: int = 1,
                     elem_bytes: int = 2) -> dict:
    """One layer's attention of a wave: ``heads x (s_pad (s_pad + 1) / 2
    + R s_pad)`` scores a row, ``2 x (d_n + d_r)`` flops a score for
    ``q . k`` and ``2 x d_v`` for ``p @ v``."""
    H, dn, dr, dv = d["heads"], d["d_n"], d["d_r"], d["d_v"]
    S = R + s_pad
    scores = H * (s_pad * (s_pad + 1) // 2 + R * s_pad)
    return {
        "flops": rows * scores * 2 * (dn + dr + dv),
        "bytes": rows * elem_bytes * (s_pad * H * (dn + dr)
                                      + S * H * (dn + dv) + S * dr
                                      + s_pad * H * dv),
    }
