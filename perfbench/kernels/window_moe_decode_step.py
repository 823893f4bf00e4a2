"""What one decode step of the window-attention / gated-expert model
needs. Bytes: every weight outside the routed banks (attention, the dense
layer, routers, shared experts, the held slice of the head: read once a
step), the held experts the step's tokens were routed to and no others,
and the key and value rows the window and the full layers can see.
Operations: two per weight outside the banks and slot, the routed
experts' per assignment that landed here, attention's per visible row."""


def bank_bytes(run) -> float:
    """The routed banks held: what a step does NOT have to read whole."""
    c, kern = run.config, run.kernel("expert_gemm")
    held = (c.get("experts_held") or [0, c["num_experts"]])[1]
    return kern.expert_layers(run) * held * kern.expert_bytes(run)


def bytes_per_step(run, steps: int) -> float:
    return (run.weight_bytes - bank_bytes(run)
            + run.kernel("expert_gemm").bytes_per_step(run, steps)
            + run.kernel("window_decode").bytes_per_step(run, steps))


def flops_per_step(run, steps: int) -> float:
    width = 2 if run.sizes["dtype"] in ("bfloat16", "float16") else 4
    dense = 2.0 * (run.weight_bytes - bank_bytes(run)) / width
    return (dense * run.config["engine"]["slots"]
            + run.kernel("expert_gemm").flops_per_step(run, steps)
            + run.kernel("window_decode").flops_per_step(run, steps))
