"""Milliseconds per study that ``Experiment.run`` spends outside the
fleet's ``stage``, ``program`` and ``unpack`` spans: the facade, the
world copy, and ``run_fleet``'s per-session result loop."""


def read(rec):
    s = rec["studies"]
    return 1e3 * sum(r["wall_s"] - r["stage"] - r["program"] - r["unpack"]
                     for r in s) / len(s)
