"""Device milliseconds of the traced study in the fleet program's
``score`` named scope: the requesters' evaluations on their test split
(``Phase.SCORE``).  Self time, as in ``fit_device_ms``."""

import spantrace


def read(rec):
    return spantrace.phase_ms(rec, "score")
