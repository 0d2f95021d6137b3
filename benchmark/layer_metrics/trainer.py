"""Layer ``trainer`` (``parallel/fleet.py:make_train_step``)."""

from harness import opcount


def mfu(obs):
    """Model FLOP/s utilization: the flops a token needs forward and
    backward (``opcount.train_flops_per_token``; no recomputation) times
    tokens per second per chip, over the chip's bf16 peak. The same
    number as ``train_tokens_per_s``, named for what it is."""
    if "peaks" not in obs:
        return None
    d = opcount.dims(obs["config"])
    flops = opcount.train_flops_per_token(d, obs["seq"])
    return 100.0 * flops * obs["host_tokens_per_s"] / obs["peaks"]["flops_bf16"]
