"""The one-token update of a matrix state with one decay a head
(``ops/ssd.py``, named ``ssd_state_update`` in the trace), one call a
Mamba-2 layer and step: per advanced slot it must read the state ``[N, d]``
float32 and write it back, read the token's ``x`` and write ``y`` (``[d]``
each), read ``B``, ``C`` (``[N]``) and the step (a head each); the decay is
``heads`` exponentials a slot and no ``[N, d]`` operand. Whatever computes
it reads the state once and writes it once. The advanced slots are the
program's own count (``state_slots``)."""

PATTERN = r"^ssd_state_update"


def state_elements(run) -> float:
    """One layer's state of one slot."""
    c = run.config
    return float(c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"])


def token_bytes(run) -> float:
    """What a slot's token brings and takes, float32."""
    c = run.config
    d = c["mamba_n_heads"] * c["mamba_d_head"]
    return 4.0 * (2 * d + 2 * c["mamba_d_state"] + c["mamba_n_heads"])


def bytes_per_step(run) -> float:
    step = run.kernel("ssd_moe_decode_step")
    per_slot = 2 * 4.0 * state_elements(run) + token_bytes(run)
    return step.mamba_layers(run) * step.per_round(run)["state_slots"] * per_slot


def flops_per_step(run) -> float:
    """Per state element the decay, the outer product's two, the read's
    two."""
    step = run.kernel("ssd_moe_decode_step")
    return (5.0 * step.mamba_layers(run) * step.per_round(run)["state_slots"]
            * state_elements(run))
