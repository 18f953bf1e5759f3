"""Model FLOPs of the train steps that ran in the traced window (``work.py``,
PaLM convention) over the window's seconds, the chips and the chips' peak
bf16 FLOP/s, in %. It counts the whole HFL period's time, syncs included."""


def read(ctx):
    t = ctx["trace"]
    steps = ctx["traced_steps"]
    if not steps:
        return None
    flops = steps * ctx["work"]["tokens_per_step"] * ctx["work"]["flops_per_token"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (t["window_ns"] / 1e9 * peak)
