"""Device milliseconds of the traced study in the fleet program's
``fit`` named scope: the requesters' own fits (``Phase.FIT``).  Self
time, so a ``while`` or ``conditional`` counts only what its body does
not."""

import spantrace


def read(rec):
    return spantrace.phase_ms(rec, "fit")
