"""Device time of the decode program per run: one run advances every
lane one token."""


def read(ctx):
    ph = ctx["phases"]
    if ph is None or ph["decode_runs"] == 0:
        return None
    return 1e3 * ph["decode"] / ph["decode_runs"]
