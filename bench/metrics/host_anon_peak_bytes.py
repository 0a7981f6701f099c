"""Peak anonymous resident memory (``RssAnon``) of the process during the
window, sampled every 50 ms: the host memory SSO exists to bound."""


def read(r):
    return r["host_anon_peak_bytes"]
