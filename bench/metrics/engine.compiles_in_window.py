"""Programs compiled or loaded from the persistent compile cache while the
window was open, by JAX's monitoring events."""


def read(run):
    return float(run.compiles_window)
