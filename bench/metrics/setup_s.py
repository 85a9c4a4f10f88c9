"""Seconds from the start of the process to the start of the window: JAX
and the chip, the compile cache, the tables made on the device, and the
warm-up unit (host clock)."""


def read(run):
    return run.setup_s
