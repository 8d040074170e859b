"""Mean milliseconds from the host's call of the refill to the chip's
start of it: from the start of each `msc.refill.call` span in the traced
window to the start of the first `jit_refill` on the first chip after
it, and before the next call.  The staging's copy to the chip lies in
it.  Device trace (the program's host spans and the chip's programs on
one clock); moves tensors_per_s."""

import bisect


def read(run):
    t = run.trace
    if t is None:
        return None
    calls = [s for s, _, _ in t.events.get("msc.refill.call", [])]
    refills = [s for s, _, name in t.programs if name == "jit_refill"]
    lo, hi = t.window
    ships = []
    for i, call in enumerate(calls):
        if not lo <= call <= hi:
            continue
        nxt = calls[i + 1] if i + 1 < len(calls) else float("inf")
        k = bisect.bisect_left(refills, call)
        if k < len(refills) and refills[k] < nxt:
            ships.append(refills[k] - call)
    return 1e3 * sum(ships) / len(ships) if ships else None
