"""The latent family's tiled prefill attention's share of the MXU's peak:
the score and value products that the admitted prompts' TRUE lengths need
under each layer's band and SELECTION (kernels/sparse_mla_decode_step.py
``prefill_flops``), over the summed device time of the
``mla_flash_prefill*`` calls inside the window's admissions. The masked
form multiplies every causal block of a full layer, so it reads lower than
a form that gathered the selected keys would, never over 100%."""
UNIT = "%"


def read(run):
    progs = run.modules("prefill")
    kern = run.kernel("sparse_mla_decode_step")
    calls = run.ops().matching(kern.PREFILL_PATTERN).inside(progs)
    if not len(progs) or not len(calls) or not kern.admissions(run):
        return None
    return (100.0 * kern.prefill_flops(run) / run.peaks["bf16_flops_per_s"]
            / calls.total_s())
