"""Share of the key and value rows a decode step's attention reads that
its WINDOW layers read: ``window_rows / (window_rows + full_rows)`` over
the window's decode rounds. It falls as contexts grow past the window:
the window layers go on reading ``window`` rows a slot, the full layers
read them all."""
UNIT = "%"


def read(run):
    window, full = run.kernel("window_decode").rows(run)
    return 100.0 * window / (window + full) if window + full else None
