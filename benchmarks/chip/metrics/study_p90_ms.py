"""The 90th percentile of the host wall time of every study in the
window (a study that runs past the window's end included)."""

import statistics


def read(rec):
    walls = [(s["t1"] - s["t0"]) * 1e3 for s in rec["studies"]]
    if len(walls) < 10:
        return None
    return statistics.quantiles(walls, n=10)[8]
