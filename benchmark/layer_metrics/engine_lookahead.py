"""Layer ``engine``: how often the tick keeps a step program in flight.

``ServingEngine.step()`` dispatches the next decode step before it pulls
the tokens of the one in flight whenever the tick is the plain steady
one; ``engine.stats["lookahead_ticks"]`` counts those ticks and
``lookahead_discarded_tokens`` the tokens such a program computed for
rows that had left by its pull. A program from before the counter
existed reads as ``None``: the metric is left off the line.
"""


def lookahead_share(obs):
    """Lookahead ticks over decode steps, in per cent."""
    s = obs["stats"]
    if "lookahead_ticks" not in s or not s.get("steps"):
        return None
    return dict(value=100.0 * s["lookahead_ticks"] / s["steps"],
                lookahead_ticks=s["lookahead_ticks"], steps=s["steps"],
                discarded_tokens=s.get("lookahead_discarded_tokens"))
