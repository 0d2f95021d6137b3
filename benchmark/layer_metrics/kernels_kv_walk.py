"""Layer ``kernels``: how much of a dense walk the paged decode kernel does.

``fused_paged_decode_step`` reads cached keys and values block by block.
Its walk is a flat list of (row, block) pairs, each row's own blocks
(``paddle_tpu.ops.fused_decode.paged_walk``); the engine sums that list's
length (``paged_walk_blocks``) over the decode steps it lands into
``engine.stats["kv_blocks_walked"]`` and, beside it, into
``kv_blocks_dense`` the slots x the longest row's blocks, which is what
walking every slot to the longest row's length covers. A program from
before the counters existed, or an engine whose step is not this kernel,
reads as ``None``: the metric is left off the line.
"""


def kv_walk_share(obs):
    """Blocks walked over blocks of the dense walk, in per cent."""
    s = obs["stats"]
    if not s.get("kv_blocks_dense"):
        return None
    steps = s.get("steps") or None
    return dict(value=100.0 * s["kv_blocks_walked"] / s["kv_blocks_dense"],
                kv_blocks_walked=s["kv_blocks_walked"],
                kv_blocks_dense=s["kv_blocks_dense"],
                blocks_a_step=steps and s["kv_blocks_walked"] / steps)
