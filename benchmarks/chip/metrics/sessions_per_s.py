"""Requester sessions completed per second: every study of the window,
over the time from the window's start to the end of its last study."""


def read(rec):
    return rec["requesters"] * len(rec["studies"]) / rec["elapsed_s"]
