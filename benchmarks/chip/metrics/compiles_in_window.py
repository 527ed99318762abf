"""Programs compiled, or loaded from the persistent cache, while the
measured window ran: the harness's own count of JAX's compile events.
Should be 0; anything else is compilation charged to the steps."""


def read(r):
    return r.compiles_in_window
