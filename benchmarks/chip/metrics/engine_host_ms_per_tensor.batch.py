"""Host milliseconds inside submit and step while the chip is idle, per
returned tensor.  Device trace (host spans and device busy time on one
clock); moves tensors_per_s."""


def read(run):
    from measures import host_ms_per_tensor

    return host_ms_per_tensor(run)
