"""The whole step's share of the chip's peak: the model's matmul
operations for the real tokens of the traced window (prompt tokens
prefilled at their own lengths, tokens decoded at their lanes' depths,
attention at each token's real depth; counted from shapes) over the
configuration's peak and the traced window."""


def read(ctx):
    red = ctx["trace"]
    flops = ctx["work"]["model_flops"]
    if flops == 0 or red.window_s <= 0:
        return None
    return 100.0 * flops / ctx["peak_flops_per_s"] / red.window_s
