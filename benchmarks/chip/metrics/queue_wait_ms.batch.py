"""Mean milliseconds a request waits in the engine's queue: from the end
of its `msc.submit` span to the start of its first `msc.admit` (the
spans' `rid`), over the requests first admitted in the traced window
whose submit the trace holds.  Program spans, from the trace;
moves tensors_per_s."""


def read(run):
    t = run.trace
    if t is None:
        return None
    submitted = {stats["rid"]: e
                 for _, e, stats in t.events.get("msc.submit", [])
                 if "rid" in stats}
    first = {}
    for s, _, stats in t.events.get("msc.admit", []):
        first.setdefault(stats.get("rid"), s)
    lo, hi = t.window
    waits = [s - submitted[rid] for rid, s in first.items()
             if rid in submitted and lo <= s <= hi and s >= submitted[rid]]
    return 1e3 * sum(waits) / len(waits) if waits else None
