"""Share of the LM cell's traced window in which no operation ran on the
device: the host's part of a step (the sync on each step's tokens, the
scheduler's bookkeeping, the submits) that the chip waits through."""


def read(ctx):
    red = ctx["trace"]
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
