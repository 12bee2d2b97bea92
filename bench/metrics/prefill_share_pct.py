"""Share of the device's busy time spent on joins: the programs that run
only on steps where requests join (the prefill at each width, the fresh
decode state it fills, the merge into the live state)."""


def read(ctx):
    ph, red = ctx["phases"], ctx["trace"]
    if ph is None or red.busy_s <= 0:
        return None
    return 100.0 * ph["join"] / red.busy_s
