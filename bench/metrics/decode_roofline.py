"""Decode's least time over its device time.  A step's least time is the
larger of its matmul operations over the configuration's peak and its
least bytes over the HBM bandwidth: the weights once, and each serving
lane's cached keys and values of its valid positions only (counted from
shapes), so a plan that stops reading padded cache cannot pass 100 %."""


def read(ctx):
    ph = ctx["phases"]
    work = ctx["work"]
    flops, nbytes = work["decode_flops"], work["decode_min_bytes"]
    if ph is None or ph["decode"] <= 0 or not flops:
        return None
    least = sum(max(f / ctx["peak_flops_per_s"], b / ctx["hbm_bytes_per_s"])
                for f, b in zip(flops, nbytes)) / len(flops)
    return 100.0 * least * ph["decode_runs"] / ph["decode"]
