"""The model's operations in the traced window over the window's length
times the chip's int8 peak, in %.

Counted from shapes (``roofline.token_ops``): every output token emitted
in the window multiplies every projection and the logits and attends to
its context; every prompt prefilled in the window (admitted, and so
prefilled, at a step that started in it) multiplies every projection and
attends causally. Padding rows and empty slots do not count: they are
work the chip did that the model did not need."""

from chipbench import roofline

UNIT = "%"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    d, dims = run.driver, run.dims
    lo, hi = d.t0, d.t_end
    ops = 0.0
    for r in d.records:
        plen = len(r.spec.prompt)
        if r.admitted is not None and lo <= r.admitted < hi:
            ops += roofline.prefill_ops(dims, plen - 1)
        for t, when in enumerate(r.times):
            if lo < when <= hi:
                ops += roofline.token_ops(dims, plen + t, True)
    return 100.0 * ops / ((hi - lo) * run.peaks["int8_ops_per_s"])
