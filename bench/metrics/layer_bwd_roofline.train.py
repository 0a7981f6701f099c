"""Share of its roofline that the backward layer program (``layer_vjp``)
reached in the traced window, in percent: the least seconds of its calls
(``bench/flops.py``, real rows and edges, the larger of FLOPs over the bf16
peak and bytes over the HBM peak) over its device seconds."""


def read(r):
    d, least = r.get("device"), r.get("least")
    if r["job"] != "train" or not d or not least:
        return None
    prog = d["programs"].get("jit_layer_vjp")
    if not prog or not prog["seconds"]:
        return None
    return (100.0 * least["jit_layer_vjp"]["seconds"] * r["iters"]
            / prog["seconds"])
