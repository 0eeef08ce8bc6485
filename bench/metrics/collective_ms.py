"""Device milliseconds per round in collective ops (collective-permute,
all-gather, all-reduce, ...), as a union of their intervals, averaged over
the chips.  Nothing to read where no collective ran."""


def read(run):
    t = run["trace"]
    if t is None or not t.n_collectives():
        return None
    return 1e3 * t.collective_s() / run["rounds"]
