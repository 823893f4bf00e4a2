"""The least time the chip could take for some work: the larger of its
operations over the peak rate and its bytes over the peak bandwidth."""


def floor_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """``(seconds, which bound holds)`` from the chip's published peaks."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "MXU") if compute >= memory else (memory, "HBM")
