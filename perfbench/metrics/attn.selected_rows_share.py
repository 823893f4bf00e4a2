"""Latent rows a decode step's full layers ATTEND over the rows that are
live for them: ``selected_rows / index_rows`` over the window's decode
rounds (the indexer scores every live row of a full layer, so
``index_rows`` is the full layers' live rows). 100% while contexts are
under ``index_topk``; ``index_topk / context`` past it."""
UNIT = "%"


def read(run):
    kern = run.kernel("sparse_mla_decode_step")
    got = kern.rounds(run)
    scored = kern.total(got, "index_rows") if got else 0
    if not scored:
        return None
    return 100.0 * kern.total(got, "selected_rows") / scored
