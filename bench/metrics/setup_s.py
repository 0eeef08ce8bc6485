"""Seconds from the process's start to the first timed round: imports, chip
check, traffic pool, job, state, compile or cache load, the check rounds."""


def read(run):
    return run["setup_s"]
