"""The unquantized model's FLOPs for every output token of the window
(projections, attention over the token's live context, unembed; see
``bench/work.forward_flops``) per second, over the chip's bf16 peak.
The same work whatever implements it."""


def read(ctx):
    if not ctx.get("model_flops") or not ctx.get("window_s") \
            or not ctx.get("peaks"):
        return None
    rate = ctx["model_flops"] / ctx["window_s"]
    return 100.0 * rate / ctx["peaks"]["bf16_flops_s"]
