"""Programs compiled or loaded from the persistent cache inside the
window (``jax.monitoring``): warm-up should leave none."""


def read(w):
    return float(w.compiles)
