"""Device milliseconds of the traced study in the fleet program's
``refresh`` named scope: the contributors' refresh fits and their
once-per-program schedule (``Phase.REFRESH``).  Self time, as in
``fit_device_ms``."""

import spantrace


def read(rec):
    return spantrace.phase_ms(rec, "refresh")
