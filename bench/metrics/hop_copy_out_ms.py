"""Host time of the ``copy_out`` phase of ``StreamLanes.hop`` (the end of
the one transfer of the packed events and logits, started at dispatch,
and their unpacking into host numpy) per window step: the program's
``cell.hop.copy_out`` span, clipped to the traced window, over the
window's steps.  A trace without the program's spans has nothing to
read."""


def read(ctx):
    t = ctx["trace"].program_s.get("cell.hop.copy_out", 0.0)
    if t <= 0 or ctx["steps"] == 0:
        return None
    return 1e3 * t / ctx["steps"]
