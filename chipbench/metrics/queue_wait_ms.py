"""Mean time from a request's scheduled arrival to its admission, seen
from the harness as the request leaving ``engine.queue`` (taken at the
start of the step that admitted it), over the requests of the traced
window, in ms."""

import numpy as np

UNIT = "ms"


def read(run):
    w = run.driver.queue_wait_ms()
    return float(np.mean(w)) if w else None
