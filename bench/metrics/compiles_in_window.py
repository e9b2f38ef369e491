"""Planner / executor layer: programs built inside the measured window
(``jax.monitoring`` backend-compile events). Every shape the window uses is
built in set-up, so anything here is a program shape that set-up did not
see. Moves ``veh_steps_per_s``."""


def read(rec):
    return float(sum(1 for t, _ in rec.compiles if rec.in_window(t)))
