"""What one decode step of the Mamba-2 / attention / expert model needs.
Bytes: every weight outside the routed banks (the mixers' projections,
routers, shared experts, the attention layer, the held slice of the
embedding, which is the head: read once a step), the HELD experts the
step's tokens were routed to and no others (``experts_hit``), each advanced
slot's recurrent state read and written (``state_slots``: a step advances
every slot of the batch, idle ones too; with it the convolution's ``d_conv
- 1`` earlier inputs read and this token's written, ``x | B | C`` wide) and
the key and value rows its attention layers can see (``kv_rows``, from the
lengths the step was given). Operations: two per weight outside the banks
and slot, six per assignment that landed here and expert matrix element
row, the recurrence's per state element, attention's per visible row. The
counters are means over the window's ROUNDS: a step sent in vain moves
what its re-run moves."""
from harness import spans as sp

COUNTERS = ("state_slots", "kv_rows", "experts_hit", "assignments")


def mamba_layers(run) -> int:
    return sum(k == "mamba" for k in
               run.config["layer_types"][: run.sizes["n_layers"]])


def state_bytes_per_slot(run) -> float:
    """What advancing one slot one token moves, all Mamba-2 layers: the
    recurrence's state read and written, the convolution's ring."""
    c = run.config
    d = c["mamba_n_heads"] * c["mamba_d_head"]
    ring = 4.0 * c["mamba_d_conv"] * (d + 2 * c["mamba_d_state"])
    return mamba_layers(run) * (
        2 * 4.0 * run.kernel("ssd_state_update").state_elements(run) + ring)


def row_bytes(run) -> float:
    """One position's key and value in one attention layer."""
    return run.kernel("window_decode").row_bytes(run)


def rounds(run) -> list:
    """The decode rounds that carry the plan's counters; none where the
    run has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if all(k in s.stats for k in COUNTERS)]


def per_round(run) -> dict:
    """Each counter's mean over the window's rounds."""
    got = rounds(run)
    return {k: sum(int(s.stats[k]) for s in got) / len(got) for k in COUNTERS}


def state_bytes_per_step(run) -> float:
    return per_round(run)["state_slots"] * state_bytes_per_slot(run)


def parts(run) -> dict:
    """The step's needed bytes by what they are."""
    gemm = run.kernel("ssd_expert_gemm")
    return dict(
        weights=run.weight_bytes - gemm.bank_bytes(run),
        experts=gemm.bytes_per_step(run),
        state=state_bytes_per_step(run),
        pages=per_round(run)["kv_rows"] * row_bytes(run))


def bytes_per_step(run) -> float:
    return sum(parts(run).values())


def flops_per_step(run) -> float:
    s, c = run.sizes, run.config
    n = per_round(run)
    width = 2 if s["dtype"] in ("bfloat16", "float16") else 4
    dense = 2.0 * parts(run)["weights"] / width
    return (dense * c["engine"]["slots"]
            + run.kernel("ssd_expert_gemm").flops_per_step(run)
            + run.kernel("ssd_state_update").flops_per_step(run)
            + n["kv_rows"] * 4.0 * s["n_q_heads"] * s["head_dim"])
