"""Share of the traced window in which no operation ran on the device, in
percent: 1 - (union of the XLA op intervals) / window."""


def read(r):
    d = r.get("device")
    if r["job"] != "train" or not d or not d["window_s"]:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
