"""Share of the serve call in which the device ran nothing though the
engine was not asleep, in the sparse latent plan's cell (a backlog: no
sleep, so every gap is the host's). The
arithmetic is ``device.idle_with_work_share``'s."""
from harness import cells

UNIT = "%"


def read(run):
    return cells.load_module("metrics", "device.idle_with_work_share").read(run)
