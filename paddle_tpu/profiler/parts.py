"""The one vocabulary of model parts.

Every program a cell runs (a serving engine's programs, a train step)
puts each equation that does arithmetic on activations or weights under
exactly one ``with part("<name>"):``. A part is a ``jax.named_scope``
called ``part.<name>``: HLO metadata, nothing at run time. On the chip a
device op's framework name holds it (``jit(serving_step)/part.attn_in/
dot_general``; the backward pass reads ``transpose(jvp(part.ffn))``), and
``profiler.xplane.part_seconds`` splits a program's device time by it.
The names are the same for every architecture, so a reader needs no
table a model. Parts do not nest.
"""

import contextlib
import re
import threading

import jax

PREFIX = "part."

PARTS = (
    "embed",      # token (and position) embedding, the read-in of streams
    "norm",       # a layer's input and post-attention norms
    "attn_in",    # q / kv / latent projections, their norms, rope, the
                  # latent x W_kvb expansion, head-major copies
    "attn",       # the attention kernel or XLA's attention, the cache write
    "attn_out",   # what follows the attention: absorbed value half, output
                  # norm and gate, W_o, the residual add
    "router",     # expert scores and the top-k
    "experts",    # the routed kernel WITH its wrapper's sort, gathers, un-sort
    "ffn",        # a dense FFN: a dense layer's, an expert layer's shared
                  # experts; the residual add
    "mix",        # Xing4.0's mHC mixers: read, write, Sinkhorn
    "select",     # SALA's block selection and token mask
    "state",      # the lightning mixer's recurrence over its state
    "layers",     # a kernel that holds WHOLE decoder layers (the llama / gpt
                  # decode step of ops.fused_decode), or its reference
    "head",       # final norm and the output projection
    "sample",     # sampling, acceptance, the next step's positions
    "loss",       # the training loss
    "optimizer",  # gradient clip, loss scale and the update
)

_FOUND = re.compile(re.escape(PREFIX) + r"([a-z_]+)")
_open = threading.local()


@contextlib.contextmanager
def part(name: str):
    """``jax.named_scope("part.<name>")`` for a name of :data:`PARTS`;
    ``ValueError`` for any other name and for a part opened inside a
    part."""
    if name not in PARTS:
        raise ValueError(f"{name!r} is not a part; the parts are {PARTS}")
    outer = getattr(_open, "name", None)
    if outer is not None:
        raise ValueError(f"part {name!r} opened inside part {outer!r}: "
                         "parts do not nest")
    _open.name = name
    try:
        with jax.named_scope(PREFIX + name):
            yield
    finally:
        _open.name = None


def part_of(op_name: str):
    """The first part named in a framework op name
    (``jit(f)/while/body/part.ffn/dot_general`` -> ``"ffn"``), or None."""
    for m in _FOUND.finditer(op_name or ""):
        if m.group(1) in PARTS:
            return m.group(1)
    return None
