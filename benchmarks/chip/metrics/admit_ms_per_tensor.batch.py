"""Mean host milliseconds of one admission: the program's `msc.admit`
span (the tensor copied into its slot of the staging), over the
admissions that start in the traced window.  Program spans, from the
trace; moves tensors_per_s."""


def read(run):
    t = run.trace
    if t is None:
        return None
    lo, hi = t.window
    admits = [e - s for s, e, _ in t.events.get("msc.admit", [])
              if lo <= s <= hi]
    return 1e3 * sum(admits) / len(admits) if admits else None
