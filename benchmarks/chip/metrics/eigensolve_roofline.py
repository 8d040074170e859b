"""The chunk step's share of the chip's roofline, in %: bytes and operations
the realized sweeps need (measures.py) at the published peaks, over the
chunk step's device time.  Device trace; moves tensors_per_s."""


def read(run):
    from measures import eigensolve_roofline

    return eigensolve_roofline(run)
