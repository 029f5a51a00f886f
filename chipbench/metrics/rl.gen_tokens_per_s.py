"""Tokens the rollout engine generated per second of the window
(``RolloutEngine.tokens_generated``, delta over the window)."""


def read(ctx):
    w = ctx["counters"]
    return w["tokens_generated"] / w["loop_s"]
