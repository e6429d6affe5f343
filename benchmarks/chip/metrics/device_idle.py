"""Share of the traced study's wall span in which the device ran no
operation, in %."""


def read(rec):
    t = rec["device_trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
