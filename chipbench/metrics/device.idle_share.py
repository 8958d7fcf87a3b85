"""Share of the traced window in which no operation ran on the device
(1 - union of device operation intervals / window), in %."""

from chipbench.trace import busy_s

UNIT = "%"


def read(run):
    t = run.trace
    if t is None or not len(t.ops.name):
        return None
    return 100.0 * (1.0 - busy_s(t) / t.window_s())
