"""Model FLOPs of the prompt and decode tokens processed in the window
over window x peak bf16 FLOP/s, in %.  FLOPs from shapes
(``flops.prefill_chunk``, ``flops.decode_step``)."""


def read(ctx):
    c = ctx.counters
    return 100.0 * (c["prefill_flops"] + c["decode_flops"]) / (
        c["loop_s"] * ctx.chips * ctx.peaks["bf16_flops_per_s"])
