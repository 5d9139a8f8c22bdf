"""Device: programs compiled or loaded from the compile cache inside the
window (a ``jax.monitoring`` listener on backend compiles).  Set-up runs
the cell's own mix until a pass compiles nothing, so what counts here is
a program shape the window made anew, such as a seal over a longer
drained prefix."""


def read(r: dict):
    return float(r["compiles"])
