"""1 − device-busy seconds / traced window seconds (the busy time is the
union of the chip's operation intervals).  Device trace; moves
tensors_per_s."""


def read(run):
    t = run.trace
    return None if t is None else 1.0 - t.busy_s / t.window_s
