"""Device milliseconds per execution of the chunk step (`jit_step`).
Device trace; moves tensors_per_s."""


def read(run):
    from measures import program_ms

    return program_ms(run, "jit_step")
