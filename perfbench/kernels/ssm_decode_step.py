"""What one decode step of the state-space / attention model needs. Bytes:
every weight held (the embedding once: it is the head; read once a step),
each advanced slot's recurrent state read and written (the program counts
the slots itself: ``state_slots`` on ``tdt.batcher.decode_round``; a step
advances every slot of the batch, idle ones too) and the key and value
rows its attention layers can see (``kv_rows``, from the lengths the step
was given). Operations: two per weight and slot, the recurrence's per
state element, attention's per visible row. The counters are means over
the window's ROUNDS: a step sent in vain moves what its re-run moves."""
from harness import spans as sp


def mamba_layers(run) -> int:
    c = run.config
    return sum(i % c["attn_layer_period"] != c["attn_layer_offset"]
               for i in range(run.sizes["n_layers"]))


def ssm_bytes(run) -> float:
    """One layer's recurrence state of one slot, float32."""
    c = run.config
    return 4.0 * c["mamba_expand"] * c["hidden"] * c["mamba_d_state"]


def state_bytes_per_slot(run) -> float:
    """What advancing one slot one token moves, all state-space layers:
    the recurrence's state read and written, the convolution's ``d_conv -
    1`` earlier inputs read and this token's written."""
    c = run.config
    conv_row = 4.0 * c["mamba_expand"] * c["hidden"]
    return mamba_layers(run) * (2 * ssm_bytes(run) + c["mamba_d_conv"] * conv_row)


def row_bytes(run) -> float:
    """One position's key and value in one attention layer."""
    return run.kernel("window_decode").row_bytes(run)


def rounds(run) -> list:
    """The decode rounds that carry the family's counters; none where the
    run has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if "state_slots" in s.stats and "kv_rows" in s.stats]


def per_round(run) -> tuple[float, float]:
    """``(state_slots, kv_rows)``, mean over the window's rounds."""
    got = rounds(run)
    return (sum(int(s.stats["state_slots"]) for s in got) / len(got),
            sum(int(s.stats["kv_rows"]) for s in got) / len(got))


def state_bytes_per_step(run) -> float:
    return per_round(run)[0] * state_bytes_per_slot(run)


def bytes_per_step(run) -> float:
    return (run.weight_bytes + state_bytes_per_step(run)
            + per_round(run)[1] * row_bytes(run))


def flops_per_step(run) -> float:
    s, c = run.sizes, run.config
    slots, rows = per_round(run)
    width = 2 if s["dtype"] in ("bfloat16", "float16") else 4
    # per state element: exp's argument, the decay, the input, two adds,
    # the output's product and sum
    recurrence = 7.0 * mamba_layers(run) * ssm_bytes(run) / 4
    return (2.0 * (run.weight_bytes / width) * c["engine"]["slots"]
            + slots * recurrence + rows * 4.0 * s["n_q_heads"] * s["head_dim"])
