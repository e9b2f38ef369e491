"""Fleet supervisor layer: host milliseconds per chunk spent in the durable
writes (checkpoint save, shard drain and audit, journal append), measured
by the harness's spans over the window. Moves ``veh_steps_per_s``."""


def read(rec):
    n = rec.chunks_in_window()
    if not n:
        return None
    from spans import DURABLE

    return 1e3 * rec.union_s(DURABLE) / n
