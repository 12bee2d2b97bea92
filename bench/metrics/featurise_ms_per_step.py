"""Device time under the stream engine's ``featurise`` scope (the MFCC
frontend), per step.  Feature-ingest cells have no such scope."""


def read(ctx):
    t = ctx["trace"].scope_s.get("featurise", 0.0)
    if t <= 0 or ctx["steps"] == 0:
        return None
    return 1e3 * t / ctx["steps"]
