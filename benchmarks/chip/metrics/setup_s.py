"""Process start to the first timed study: imports, the world, compiling
or loading the programs, and the warm-up study."""


def read(rec):
    return rec["setup_s"]
