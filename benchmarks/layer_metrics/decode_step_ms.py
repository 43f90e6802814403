"""Device time of one execution of the decode program (jit_decode), median
over the traced window."""


def read(run):
    t = run["trace"]
    m = (t or {}).get("modules", {}).get("jit_decode")
    return None if not m else m["median_s"] * 1e3
