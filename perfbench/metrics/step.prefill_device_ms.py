"""Device time of the prefill programs per admission, fullest device."""
UNIT = "ms"


def read(run):
    ev = run.modules("prefill")
    return ev.total_s() / len(ev) * 1e3 if len(ev) else None
