"""The model's matmul operations per lane-hop (patch embedding, encoder
over the window, head; counted from shapes), times the lane-hops the traced
window completed per second, over the configuration's peak."""


def read(ctx):
    red = ctx["trace"]
    if ctx["lane_hops"] == 0 or red.window_s <= 0:
        return None
    work = ctx["work"]
    ops_per_s = work["step_flops"] * ctx["lane_hops"] / ctx["chunk_hops"] \
        / red.window_s
    return 100.0 * ops_per_s / ctx["peak_ops_per_s"]
