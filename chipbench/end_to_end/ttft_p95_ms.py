"""95th percentile, over every request sent in the window, of the time
from its scheduled arrival to the step that delivered its first token
(those still waiting at the close are served after it and count too)."""

import numpy as np

UNIT = "ms"


def read(run):
    t = run.driver.ttft_ms()
    return float(np.percentile(t, 95)) if t else None
