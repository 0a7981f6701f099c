"""Model FLOPs of the window's passes (forward) over the window's seconds and
the chip's bf16 peak, in percent."""


def read(r):
    if r["job"] != "infer" or not r.get("peaks"):
        return None
    return (100.0 * r["model_flops"] * r["iters"] / r["window_s"]
            / r["peaks"]["bf16_flops"])
