"""step.mfu.batch: the model operations of the tokens prefilled and decoded
in the traced window (with attention over the positions each attended)
over the window's seconds times the chip's peak for the recipe's matmuls
(int8 for w8a8, bf16 for w8a16)."""


def read(ctx):
    if ctx.peaks is None:
        return None
    w = ctx.work_in_trace()
    ops = ctx.model_flops(w["prefill"] + w["decode"], w["attended"],
                          w["heads"])
    peak = ctx.peaks[ctx.config["precision"]["matmul_peak"]]
    return 100.0 * ops / (ctx.traced_seconds() * peak)
