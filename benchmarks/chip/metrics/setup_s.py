"""Seconds from the start of the process to the opening of the window:
imports, the tensors made on the chip, the engine built, its programs
compiled or loaded from the cache, and the warm-up traffic.  Host clock."""


def read(run):
    return run.setup_s
