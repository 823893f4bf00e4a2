"""How uneven a step's routing is: the largest count of assignments on one
expert in any expert layer (``expert_load_max``) over the mean count an
expert of a layer gets (``assignments`` / expert layers / experts), mean
over the decode rounds that routed anything."""
UNIT = "ratio"


def read(run):
    rounds = [s for s in run.kernel("expert_gemm").rounds(run)
              if int(s.stats["assignments"]) > 0]
    if not rounds:
        return None
    c = run.config
    layers = run.kernel("expert_gemm").expert_layers(run)
    ratios = [
        int(s.stats["expert_load_max"])
        / (int(s.stats["assignments"]) / layers / c["n_routed_experts"])
        for s in rounds
    ]
    return sum(ratios) / len(ratios)
