"""Device milliseconds of the traced study in the fleet program's
``aggregate`` named scope: the batched FedAvg kernel, its wrapper's
padding and the masked-weight arithmetic (``Phase.COLLECT`` and
``Phase.AGGREGATE``).  Self time, as in ``fit_device_ms``."""

import spantrace


def read(rec):
    return spantrace.phase_ms(rec, "aggregate")
