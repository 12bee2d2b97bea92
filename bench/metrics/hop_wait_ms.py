"""Host time of the ``wait`` phase of ``StreamLanes.hop`` (blocking until
the hop's events are computed) per window step: the program's
``cell.hop.wait`` span, clipped to the traced window, over the window's
steps.  A trace without the program's spans has nothing to read."""


def read(ctx):
    t = ctx["trace"].program_s.get("cell.hop.wait", 0.0)
    if t <= 0 or ctx["steps"] == 0:
        return None
    return 1e3 * t / ctx["steps"]
