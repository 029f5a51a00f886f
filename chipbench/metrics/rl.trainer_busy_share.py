"""Share of the window the trainer thread spent inside its step
(``ThreadedRuntime.trainer_busy_s``, delta over the window)."""


def read(ctx):
    w = ctx["counters"]
    return 100.0 * w["trainer_busy_s"] / w["loop_s"]
