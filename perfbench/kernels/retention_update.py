"""The one-token state update (``ops/retention.py``, named
``retention_update`` in the trace), one call a layer and step: per advanced
slot it must read every kv head's state (``S [D, d]``, ``Z [d, d]``,
float32) and write it back, read the token's ``q`` (``h_q`` heads), ``k``,
``v`` and gate and write ``y`` (``[d]`` float32 each). The advanced slots
are the program's own count (``state_slots``). ``peaks.json`` has no peak
of the vector unit, and none is guessed: the operations stand against the
MXU's peak, which never binds here, so the share is of the HBM rate."""

PATTERN = r"^retention_update"


def bytes_per_step(run) -> float:
    step = run.kernel("retention_decode_step")
    s = run.sizes
    token = 4.0 * s["head_dim"] * (2 * s["n_q_heads"] + 3 * s["n_kv_heads"])
    return step.slots_per_round(run) * (
        step.state_bytes_per_slot(run) + s["n_layers"] * token)


def flops_per_step(run) -> float:
    step = run.kernel("retention_decode_step")
    s = run.sizes
    group = s["n_q_heads"] // s["n_kv_heads"]
    return (step.slots_per_round(run) * s["n_layers"] * (4.0 + 2.0 * group)
            * step.state_elements(run))
