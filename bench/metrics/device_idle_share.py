"""Device layer: share of the traced window in which no operation ran on
the chip (1 - busy union / window, averaged over the chips used). Moves
``veh_steps_per_s``."""


def read(rec):
    d = rec.device
    if not d or not d.get("window_s"):
        return None
    return 1.0 - d["busy_s"] / d["window_s"]
