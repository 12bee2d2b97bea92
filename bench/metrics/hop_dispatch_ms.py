"""Host time of the ``dispatch`` phase of ``StreamLanes.hop`` (the live
operand tree and the enqueue of the hop's programs) per window step: the
program's ``cell.hop.dispatch`` span, clipped to the traced window, over
the window's steps.  A trace without the program's spans has nothing to
read."""


def read(ctx):
    t = ctx["trace"].program_s.get("cell.hop.dispatch", 0.0)
    if t <= 0 or ctx["steps"] == 0:
        return None
    return 1e3 * t / ctx["steps"]
