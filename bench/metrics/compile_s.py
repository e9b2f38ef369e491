"""Launcher layer: seconds the backend spent building programs before the
window (compiles and loads from the persistent cache, as ``jax.monitoring``
reports them). Moves ``setup_s``."""


def read(rec):
    secs = [s for t, s in rec.compiles if t < rec.window[0]]
    return sum(secs) if secs else None
