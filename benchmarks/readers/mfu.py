"""The whole step's share of the chip's peak: the model's operations in the
window (from shapes, ``flops.py``; recomputation not counted) over window x
chips x bf16 peak."""


def read(ctx, flops_key: str = "model_flops"):
    ops = ctx["stats"].get(flops_key)
    if not ops or not ctx["peaks"]:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ops / (ctx["window_s"] * peak)
