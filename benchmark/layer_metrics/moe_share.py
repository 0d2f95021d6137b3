"""Layer ``engine``: how much of a decode step's routing lands on the
experts held here, for a model that is one chip's share of an
expert-parallel layer.

The step program counts ``moe_picks`` (active rows x top_k, summed over
the expert layers: every pick, wherever its expert lives) beside
``moe_rows`` (the picks that fell on held experts); ``engine.stats``
sums both over the window. Work that quietly stops arriving here reads
as a falling share, not as a gain. A program that counts no
``moe_picks`` (a model that holds every expert; the parent) reads as
``None``: the metric is left off the line.
"""


def moe_local_share(obs):
    s = obs["stats"]
    if not s.get("moe_picks") or not s.get("moe_layer_steps"):
        return None
    return dict(value=100.0 * s["moe_rows"] / s["moe_picks"],
                picks_a_layer_step=s["moe_picks"] / s["moe_layer_steps"],
                rows_here_a_layer_step=s["moe_rows"] / s["moe_layer_steps"])
