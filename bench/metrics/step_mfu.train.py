"""Model FLOPs of the window's epochs (forward plus twice forward; the
regather recompute not counted) over the window's seconds and
the chip's bf16 peak, in percent."""


def read(r):
    if r["job"] != "train" or not r.get("peaks"):
        return None
    return (100.0 * r["model_flops"] * r["iters"] / r["window_s"]
            / r["peaks"]["bf16_flops"])
