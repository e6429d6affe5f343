"""Milliseconds per study in the program's ``unpack`` span
(``repro.core.fleet.run_fleet``), over the window's studies."""


def read(rec):
    return 1e3 * sum(s["unpack"] for s in rec["studies"]) / len(rec["studies"])
