"""paddle_tpu — a TPU-native deep-learning framework.

A brand-new framework built on JAX/XLA/Pallas with the capability surface of
PaddlePaddle (reference: salemmohammed/Paddle): a ``Layer``/optimizer/AMP user
API, Fleet-style hybrid parallelism (DP, ZeRO sharding stages 1-3, Megatron
TP+SP, 1F1B pipeline, MoE expert parallel, ring-attention long context) over a
named TPU mesh, a semi-auto ``shard_tensor``/``Engine`` API lowering to GSPMD,
Pallas fusion kernels, and first-class checkpointing/profiling/observability.

Design (see SURVEY.md §7): the compute path is jnp/XLA under ``jax.jit``;
parallelism is expressed as named-mesh shardings compiled by GSPMD; the hot
fusion ops (flash attention, rms_norm, rope, fused decode step) are Pallas
TPU kernels with XLA fallbacks.
"""

from paddle_tpu import version as _version

__version__ = _version.__version__

# Core tensor veneer --------------------------------------------------------
from paddle_tpu.tensor import (  # noqa: F401
    Tensor,
    to_tensor,
    zeros,
    zeros_like,
    ones,
    ones_like,
    full,
    full_like,
    arange,
    linspace,
    empty,
    empty_like,
    eye,
    rand,
    randn,
    randint,
    randperm,
    normal,
    uniform,
    concat,
    stack,
    split,
    chunk,
    reshape,
    transpose,
    squeeze,
    unsqueeze,
    flatten,
    cast,
    matmul,
    bmm,
    add,
    subtract,
    multiply,
    divide,
    pow,
    sqrt,
    rsqrt,
    exp,
    log,
    abs,
    clip,
    maximum,
    minimum,
    mean,
    sum,
    max,
    min,
    prod,
    argmax,
    argmin,
    cumsum,
    where,
    equal,
    not_equal,
    greater_than,
    greater_equal,
    less_than,
    less_equal,
    logical_and,
    logical_or,
    logical_not,
    isnan,
    isinf,
    isfinite,
    tanh,
    sigmoid,
    sin,
    cos,
    floor,
    ceil,
    round,
    sign,
    topk,
    sort,
    argsort,
    gather,
    take_along_axis,
    scatter,
    tile,
    expand,
    roll,
    flip,
    tril,
    triu,
    diag,
    einsum,
    norm,
    dot,
    outer,
    var,
    std,
    all,
    any,
    unique,
    nonzero,
    masked_select,
    index_select,
    numel,
    shape,
)

from paddle_tpu.core.rng import seed, get_rng_state, set_rng_state  # noqa: F401
from paddle_tpu.core.flags import set_flags, get_flags  # noqa: F401
from paddle_tpu.core.dtype import (  # noqa: F401
    float32,
    float16,
    bfloat16,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
    bool_,
    complex64,
    set_default_dtype,
    get_default_dtype,
)
from paddle_tpu.core import device  # noqa: F401
from paddle_tpu.core.device import set_device, get_device, is_compiled_with_tpu  # noqa: F401
from paddle_tpu.framework.io import save, load  # noqa: F401
from paddle_tpu.framework.grad import no_grad, grad  # noqa: F401
from paddle_tpu import jit  # noqa: F401  (module: jit.to_static/save/load)

from paddle_tpu import nn  # noqa: F401
from paddle_tpu.nn.layer import LazyGuard  # noqa: F401  (paddle.LazyGuard)
from paddle_tpu import optimizer  # noqa: F401
from paddle_tpu import amp  # noqa: F401
from paddle_tpu import io  # noqa: F401
from paddle_tpu import ops  # noqa: F401
from paddle_tpu import parallel  # noqa: F401
# Paddle-style alias: paddle.distributed.* (also importable as a module path)
import sys as _sys
from paddle_tpu import parallel as distributed  # noqa: F401
_sys.modules[__name__ + ".distributed"] = distributed
from paddle_tpu import linalg  # noqa: F401
from paddle_tpu import fft  # noqa: F401
from paddle_tpu import signal  # noqa: F401  (paddle.signal stft/istft)
from paddle_tpu import quantization  # noqa: F401
from paddle_tpu import regularizer  # noqa: F401
from paddle_tpu import metric  # noqa: F401
from paddle_tpu import audio  # noqa: F401
from paddle_tpu import distribution  # noqa: F401
from paddle_tpu import sparse  # noqa: F401
from paddle_tpu import models  # noqa: F401
from paddle_tpu import inference  # noqa: F401
from paddle_tpu import incubate  # noqa: F401
from paddle_tpu import vision  # noqa: F401
from paddle_tpu import profiler  # noqa: F401
from paddle_tpu import observability  # noqa: F401
from paddle_tpu import resilience  # noqa: F401
from paddle_tpu import serving  # noqa: F401
from paddle_tpu import utils  # noqa: F401
from paddle_tpu.parallel.data_parallel import DataParallel  # noqa: F401
