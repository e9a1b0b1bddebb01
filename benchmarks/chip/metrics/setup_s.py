"""setup_s: process start to the measured window's start (host clock):
weights made on the device, pruning and plan compile where the
configuration has them, engine build and warm-up."""


def read(run):
    return run.setup_s
