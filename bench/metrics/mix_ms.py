"""Device milliseconds per round of the ops under the named scope
``repro/mix``: the gossip mix (collective-permutes, the neighbour
combination, any channel's codec work).  An asynchronous collective counts
by its own ops, its ``-done``'s wait included, not by its time in flight
(``collective_ms``).  A union of their intervals, averaged over the chips;
nothing where no op carries the scope."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    s = t.scope_s("repro/mix")
    return 1e3 * s / run["rounds"] if s > 0 else None
