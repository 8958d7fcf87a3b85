"""Seconds from the start of the process to the opening of the window:
loading, weights made on the device, engine built, every shape the
window uses compiled (or read from the cache) and run once."""

UNIT = "s"


def read(run):
    return run.setup_s
