"""Device time of the decode-step program per execution, fullest device."""
UNIT = "ms"


def read(run):
    ev = run.modules("decode_step")
    return ev.total_s() / len(ev) * 1e3 if len(ev) else None
