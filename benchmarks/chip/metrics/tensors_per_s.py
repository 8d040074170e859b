"""Tensors served per second of the window.  Host clock.

Every returned request counts the share of its service (from the start of
the tick that admitted it to the end of the tick that ran its last chunk)
that falls in the window, so the count is all the work of the window and
does not jump by a burst of returns with the phase at which the window
opens or closes; it is divided by the window's seconds."""


def read(run):
    from measures import served_tensors

    w0, w1 = run.window
    return served_tensors(run) / (w1 - w0)
