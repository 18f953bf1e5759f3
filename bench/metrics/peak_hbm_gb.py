"""Peak device memory on the fullest chip after the window, in GB (1e9
bytes): the runtime's ``peak_bytes_in_use``, which counts buffers, plus its
``peak_bytes_reserved``, which holds the programs' temporaries."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return None if b is None else b / 1e9
