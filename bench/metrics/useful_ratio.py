"""Simulator layer: live vehicle-steps over the slot-steps the chunk plans
computed in the window (rows gathered, padding included, times chunk steps
times vehicle slots). Moves ``veh_steps_per_s``."""


def read(rec):
    first, last = rec.window_chunks
    slot_steps = sum(s for c, s in rec.plans if first <= c < last)
    return rec.veh_steps / slot_steps if slot_steps else None
