"""95th percentile of the gaps between consecutive output tokens of one
request, over every such gap whose two tokens both came in the window.

The gap from a closed loop's last set-up token to its first token in the
window spans the end of set-up and is not counted (``Driver.itl_ms``)."""

import numpy as np

UNIT = "ms"


def read(run):
    gaps = run.driver.itl_ms()
    return float(np.percentile(gaps, 95)) if gaps else None
