"""The encoder's least time over its device time under the ``encode``
scope.  The least time is the larger of its matmul operations over the
configuration's peak and its least bytes over the HBM bandwidth, both
counted from the algorithm's shapes, so it reads the same work whatever
kernels or fusions implement it."""


def read(ctx):
    t = ctx["trace"].scope_s.get("encode", 0.0)
    if t <= 0 or ctx["steps"] == 0:
        return None
    work = ctx["work"]
    per_step = max(
        work["encoder_flops"] * ctx["lanes"] / ctx["peak_ops_per_s"],
        work["encoder_min_bytes"] / ctx["hbm_bytes_per_s"])
    return 100.0 * per_step * ctx["steps"] / t
