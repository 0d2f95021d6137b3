"""Fusion ops — the TPU stand-ins for the reference's phi fusion kernels.

Reference (SURVEY.md §2.2): paddle/phi/kernels/fusion/gpu/
{fused_multi_transformer_op.cu, fused_rope_kernel.cu, rms_norm_kernel.cu},
phi/kernels/gpu/flash_attn_kernel.cu. Here each op has (a) an XLA path —
a jnp composition XLA fuses well — and (b) a Pallas TPU kernel for the cases
where hand-tiling beats the compiler (long-seq attention). Dispatch is
centralized in `use_pallas()`.
"""

import jax

from paddle_tpu.core.flags import flag


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    return bool(flag("FLAGS_use_pallas_kernels")) and on_tpu()


def pallas_mode() -> tuple:
    """(run the Mosaic kernels, in interpret mode): on a TPU the kernels
    as compiled; elsewhere only under ``FLAGS_pallas_interpret``."""
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    interp = bool(flag("FLAGS_pallas_interpret")) and not use_pallas()
    return use_pallas() or interp, interp


from paddle_tpu.ops import flash_attention  # noqa: F401,E402
from paddle_tpu.ops import rms_norm  # noqa: F401,E402
from paddle_tpu.ops import rope  # noqa: F401,E402
from paddle_tpu.ops.rope import fused_rotary_position_embedding  # noqa: F401,E402
from paddle_tpu.ops.flash_attention import flash_attention as flash_attn  # noqa: F401,E402


def tied_unembed(x, embed_w):
    """Unembedding against a TIED embedding table (vocab, h): contract
    the hidden dim directly — `x @ embed_w.T` materializes a (h, vocab)
    transposed copy every step (measured 0.12 ms at gpt2-medium decode,
    r5 profile)."""
    import jax

    return jax.lax.dot_general(x, embed_w, (((x.ndim - 1,), (1,)), ((), ())))
