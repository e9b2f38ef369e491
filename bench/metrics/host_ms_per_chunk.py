"""Planner / executor layer: host milliseconds per chunk inside
``run_chunk`` (bitmap sync, planning, gather/scatter and program enqueue),
measured by the harness's spans over the window. Moves
``veh_steps_per_s``."""


def read(rec):
    n = rec.chunks_in_window()
    if not n:
        return None
    from spans import HOST

    return 1e3 * rec.union_s(HOST) / n
