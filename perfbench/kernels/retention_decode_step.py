"""What one decode step of the power-retention model needs. Bytes: every
weight held (the layers and the untied head; read once a step) and each
advanced slot's state READ AND WRITTEN, every layer: a kv head's ``S [D,
d]`` and ``Z [d, d]``, float32, ``D = 32 (d / 8) (d / 8 + 1)`` rows (the
symmetric square of a ``d``-wide key in whole ``[8, d]`` tiles: 8704 at
128). The state is read once and written once a step and advanced slot,
which is the form served (a form that folded a chunk of tokens in at once
would write it once a chunk: a lower count, and another benchmark's). The
program counts the slots itself (``state_slots`` on
``tdt.batcher.decode_round``; a step advances every slot of the batch, idle
ones too). Nothing of it grows with the context. Operations: two per weight
and slot; per state element and slot the decay, the outer product's two
and, for each query head of the group, the read's two. The counter is a
mean over the window's ROUNDS: a step sent in vain moves what its re-run
moves."""
from harness import spans as sp


def state_rows(d: int) -> int:
    return 32 * (d // 8) * (d // 8 + 1)


def state_elements(run) -> float:
    """One layer's state of one slot: ``S`` and ``Z`` of every kv head."""
    s = run.sizes
    d = s["head_dim"]
    return float(s["n_kv_heads"] * (state_rows(d) + d) * d)


def state_bytes_per_slot(run) -> float:
    """What advancing one slot one token moves, all layers: the state
    read and written, float32."""
    return run.sizes["n_layers"] * 2 * 4.0 * state_elements(run)


def rounds(run) -> list:
    """The decode rounds that carry the family's counters; none where the
    run has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if "state_slots" in s.stats and "prompt_chunks" in s.stats]


def slots_per_round(run) -> float:
    got = rounds(run)
    return sum(int(s.stats["state_slots"]) for s in got) / len(got)


def state_bytes_per_step(run) -> float:
    return slots_per_round(run) * state_bytes_per_slot(run)


def bytes_per_step(run) -> float:
    return run.weight_bytes + state_bytes_per_step(run)


def flops_per_step(run) -> float:
    s, c = run.sizes, run.config
    width = 2 if s["dtype"] in ("bfloat16", "float16") else 4
    group = s["n_q_heads"] // s["n_kv_heads"]
    per_element = 4.0 + 2.0 * group
    return (2.0 * (run.weight_bytes / width) * c["engine"]["slots"]
            + slots_per_round(run) * s["n_layers"] * per_element
            * state_elements(run))
