"""Peak device memory in use after the window, largest over the cell's
chips (``peak_bytes_in_use`` of the runtime), in GB."""


def value(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
