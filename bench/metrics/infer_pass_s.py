"""Window seconds over the offloaded inference passes over every node
completed in it (each ending with every embedding on storage)."""


def read(r):
    return r["window_s"] / r["iters"] if r["job"] == "infer" else None
