"""Share of the roofline the ``pqs_dot`` Pallas kernels reach, in %: the
sum over the kernel calls in the traced window of the least time each
could take (the larger of its operations over the int8 peak and its bytes
over the HBM bandwidth; ``roofline.call_from_hlo`` reads them from the
call's shapes as the trace names it) over the sum of their device time.

The calls are the compiled policy kernels behind ``pqs_dot``
(``seq_policy_matmul`` and the N:M ``nm_*policy_matmul``), matched by
their HLO instruction names."""

import re

from chipbench import roofline

UNIT = "%"

KERNEL = re.compile(r"^%\w*policy_matmul\.\d+ = .*tpu_custom_call")


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    best = spent = 0.0
    for name, dur in zip(t.ops.name, t.ops.dur()):
        if not KERNEL.match(name):
            continue
        work = roofline.call_from_hlo(name)
        if work is None:
            return None
        best += roofline.least_time(*work, run.peaks)[0]
        spent += float(dur)
    return 100.0 * best / spent if spent else None
