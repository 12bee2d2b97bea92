"""Device time under the stream engine's ``encode`` scope, per step."""


def read(ctx):
    t = ctx["trace"].scope_s.get("encode", 0.0)
    if t <= 0 or ctx["steps"] == 0:
        return None
    return 1e3 * t / ctx["steps"]
