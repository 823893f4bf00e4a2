"""The one-token state update (``ops/selective_scan.py``, named
``selective_state_update`` in the trace), one call a state-space layer and
step: per advanced slot it must read the recurrence's state ``[N, d]``
float32 and write it back, read the token's ``c`` and ``dt`` and write
``y`` (``[d]`` each); ``A`` and ``D`` are read once a call. The advanced
slots are the program's own count (``state_slots``)."""

PATTERN = r"^selective_state_update"


def bytes_per_step(run) -> float:
    step = run.kernel("ssm_decode_step")
    c = run.config
    d = c["mamba_expand"] * c["hidden"]
    per_slot = 2 * step.ssm_bytes(run) + 3 * 4.0 * d
    once = step.ssm_bytes(run) + 4.0 * d                    # A, D
    return step.mamba_layers(run) * (step.per_round(run)[0] * per_slot + once)


def flops_per_step(run) -> float:
    step = run.kernel("ssm_decode_step")
    return (7.0 * step.mamba_layers(run) * step.per_round(run)[0]
            * step.ssm_bytes(run) / 4)
