"""The part of ``collective_ms`` in which no other op ran on that device:
communication not hidden behind compute, per round, averaged over chips."""


def read(run):
    t = run["trace"]
    if t is None or not t.n_collectives():
        return None
    return 1e3 * t.collective_exposed_s() / run["rounds"]
