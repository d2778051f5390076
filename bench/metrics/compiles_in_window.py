"""Programs compiled or loaded while the window ran (JAX's backend
compile events), counted on the host."""


def read(rec):
    return float(rec.compiles_in_window)
