"""Mean device time of one execution of the engine's jitted decode step
(``jit_step`` on the trace's XLA Modules line), in ms."""

UNIT = "ms"


def read(run):
    t = run.trace
    if t is None:
        return None
    m = t.modules.named(lambda n: n.split("(")[0] == "jit_step")
    return 1e3 * float(m.dur().mean()) if len(m.name) else None
