"""Window seconds over the SSO epochs completed in it (forward, loss and
regathering backward, each ending with its results on the host)."""


def read(r):
    return r["window_s"] / r["iters"] if r["job"] == "train" else None
