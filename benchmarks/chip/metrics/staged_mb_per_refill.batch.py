"""Megabytes (1e6 B) of host arrays each refill hands the chip: the
program's ServeStats staged_bytes over its refills, both over the window.
Program counters; moves tensors_per_s.  A program without the counter
reads nothing."""


def read(run):
    staged = run.counters.get("staged_bytes")
    refills = run.counters.get("refills")
    return staged / refills / 1e6 if staged is not None and refills else None
