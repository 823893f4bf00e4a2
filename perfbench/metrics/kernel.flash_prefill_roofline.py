"""The tiled prefill attention's share of the MXU's peak: the score and
value products that the admitted prompts' TRUE lengths need under each
layer's band (kernels/flash_prefill.py), over the summed device time of
the ``flash_prefill*`` calls inside the window's admissions."""
UNIT = "%"


def read(run):
    progs = run.modules("prefill")
    kern = run.kernel("flash_prefill")
    calls = run.ops().matching(kern.PATTERN).inside(progs)
    if not len(progs) or not len(calls) or not kern.admissions(run):
        return None
    return (100.0 * kern.flops(run) / run.peaks["bf16_flops_per_s"]
            / calls.total_s())
