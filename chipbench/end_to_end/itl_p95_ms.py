"""95th percentile of the gaps between consecutive output tokens of one
request, over every such gap whose later token came in the window."""

import numpy as np

UNIT = "ms"


def read(run):
    gaps = run.driver.itl_ms()
    return float(np.percentile(gaps, 95)) if gaps else None
