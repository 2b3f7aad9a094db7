"""Model FLOPs of the window's whole training steps over window x chips
x peak bf16 FLOP/s, in %.  FLOPs from shapes (the
architecture's ``train_step``)."""


def read(ctx):
    c = ctx.counters
    return 100.0 * c["flops"] / (c["window_s"] * ctx.chips
                                 * ctx.peaks["bf16_flops_per_s"])
