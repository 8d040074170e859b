"""Busy slot-chunks over dispatched slot-chunks in the window: the share
of the chunk step spent on live requests.  Program counters (ServeStats
busy_slot_chunks / slot_chunks); moves tensors_per_s."""


def read(run):
    c = run.counters
    return c["busy_slot_chunks"] / c["slot_chunks"] if c["slot_chunks"] else None
