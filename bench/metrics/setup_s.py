"""Seconds from the start of the process to the first timed call: device
init, inputs from the seed, programs compiled or loaded, the warm call."""


def read(run):
    return run.setup_s
