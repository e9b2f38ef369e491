"""Neighbour engine layer: device milliseconds of one lane-table build over
every instance of the window's last state, by the program's engine
(``repro.core.neighbors.build_tables``, vmapped and jitted by the harness,
timed from its own trace). A stand-in until the program names the build
inside its step. Moves ``veh_steps_per_s``."""


def read(rec):
    s = rec.neighbor_build_s
    return None if not s else 1e3 * s
