"""Key and value rows a decode step's attention reads, over what it would
read if every layer attended in full at the same lengths:
``(window_rows + full_rows) / (n_layers x the slots' summed lengths)``
over the window's decode rounds (the lengths are ``full_rows`` over the
number of full layers). Under 100% where a window clips."""
UNIT = "%"


def read(run):
    window, full = run.kernel("window_decode").rows(run)
    layout = run.config.get("sliding_window_layout", [])[: run.sizes["n_layers"]]
    n_full = sum(1 for x in layout if not x)
    if not full or not n_full:   # no such counter, or no such plan
        return None
    return 100.0 * (window + full) / (full / n_full * len(layout))
