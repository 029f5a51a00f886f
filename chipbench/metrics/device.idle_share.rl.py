"""Share of the traced window in which no operation ran on the device:
1 minus the union of device-operation intervals over the window, the
mean over the chips used (``tracereduce.reduce``)."""


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
