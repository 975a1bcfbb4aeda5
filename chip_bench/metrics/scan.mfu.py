"""Model FLOP utilisation of the fused round scan, in %: the FLOPs of
local training (forward + backward = 3x the forward) and of the in-scan
evaluation, counted from the cell's sizes by the cost model, over the
summed `fused_scan` span seconds x chips x the chip's bf16 peak."""


def read(ctx):
    scan_s = sum(r["spans"].get("fused_scan", 0.0) for r in ctx["runs"])
    if scan_s <= 0:
        return None
    w = ctx["work"]
    flops = (w["train_flops"] + w["eval_flops"]) * len(ctx["runs"])
    return 100.0 * flops / (scan_s * ctx["chips"] * ctx["peaks"]["bf16_flops"])
