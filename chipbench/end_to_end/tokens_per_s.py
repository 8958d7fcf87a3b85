"""Output tokens emitted in the window over the window's length (the
window closes at the first step boundary after ``--seconds``)."""

UNIT = "tokens/s"


def read(run):
    return run.driver.tokens_in_window() / run.driver.window_s()
