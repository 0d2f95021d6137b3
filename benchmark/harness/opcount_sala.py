"""Operations and bytes MiniCPM-SALA's five kernels need, from the
published sizes alone (``reference_minicpm_sala.dims``).

They count what the mathematics requires: true tokens, active rows, the
selected set exactly. A kernel that computes masked scores, reads idle
slots' states or walks unused list entries does more and scores lower.

* ``blocks_read(d, n)``: the blocks of ``block_size`` tokens a query
  with n visible tokens reads, a KV group: every block up to its own if
  ``n <= dense_len``, else ``init_blocks`` + the window + the ``topk``
  (or as many others as there are).
* ``keys_read(d, n)``: the tokens in them that are ``<=`` the query's
  position: whole blocks but the last, which is the query's own.
* a lightning head's recurrence a token: decay, outer product and add
  (3 d^2) and the output (2 d^2).
"""

import numpy as np


def blocks_read(d: dict, n):
    """n: tokens visible to the query, itself included (array or int)."""
    n = np.asarray(n, np.int64)
    bs = d["block_size"]
    visible = (n - 1) // bs + 1
    forced = d["init_blocks"] + d["window_size"] // bs
    sparse = forced + np.minimum(d["topk"], np.maximum(visible - forced, 0))
    return np.where(n <= d["dense_len"], visible, np.minimum(sparse, visible))


def keys_read(d: dict, n):
    n = np.asarray(n, np.int64)
    bs = d["block_size"]
    return (blocks_read(d, n) - 1) * bs + (n - 1) % bs + 1


def compressed_keys(d: dict, n):
    """The compressed keys a query with n visible tokens scores."""
    n = np.asarray(n, np.int64)
    return np.maximum((n - d["kernel_size"]) // d["kernel_stride"] + 1, 0)


def lightning_decode_call(d: dict, rows: float, elem_bytes: int = 2) -> dict:
    """One lightning layer of one decode step over ``rows`` active rows:
    each row's float32 state read and written, q, k, v in, o (float32)
    out."""
    H, dd = d["l_heads"], d["l_head_dim"]
    return {"flops": rows * H * 5 * dd * dd,
            "bytes": rows * H * (2 * 4 * dd * dd + 3 * elem_bytes * dd
                                 + 4 * dd)}


def lightning_prefill_call(d: dict, tokens: float, rows: float = 1,
                           elem_bytes: int = 2) -> dict:
    """One lightning layer over ``tokens`` true tokens of ``rows``
    requests (all the chunks of a wave together): the recurrence a
    token, q, k, v in, o (float32) out, each row's state in and out once
    a call (``calls`` chunks do that ``calls`` times; the count is the
    least: once)."""
    H, dd = d["l_heads"], d["l_head_dim"]
    return {"flops": tokens * H * 5 * dd * dd,
            "bytes": tokens * H * dd * (3 * elem_bytes + 4)
            + rows * H * 2 * 4 * dd * dd}


def sparse_decode_call(d: dict, n, elem_bytes: int = 2) -> dict:
    """One sparse layer of one decode step, selection and walk together,
    for rows that see ``n`` tokens each (an array): the compressed keys
    scored, then k and v of the tokens read, a KV group; q in, o out."""
    n = np.asarray(n, np.int64)
    H, G, dd = d["heads"], d["kv_heads"], d["head_dim"]
    ck = compressed_keys(d, np.where(n <= d["dense_len"], 0, n)).sum()
    keys = keys_read(d, n).sum()
    return {"flops": float(2 * H * dd * ck + 4 * H * dd * keys),
            "bytes": float(elem_bytes * G * dd * (ck + 2 * keys)
                           + len(n) * H * dd * (elem_bytes + 4))}


def sparse_prefill_call(d: dict, true_len: int, elem_bytes: int = 2) -> dict:
    """One sparse layer's attention over a request of ``true_len``
    tokens (all the chunks of its wave together): every query's scores
    and weighted values over the selected set exactly; q in, k and v of
    every position once, o (float32) out."""
    H, G, dd = d["heads"], d["kv_heads"], d["head_dim"]
    keys = int(keys_read(d, np.arange(1, true_len + 1)).sum())
    return {"flops": 4 * H * dd * keys,
            "bytes": true_len * (H * dd * (elem_bytes + 4)
                                 + 2 * G * dd * elem_bytes)}
