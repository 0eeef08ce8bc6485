"""Device milliseconds per round of the ops under the named scope
``repro/local_update`` (the tau-1 local DSE-MVR steps), as a union of
their intervals, averaged over the chips."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    s = t.scope_s("repro/local_update")
    return 1e3 * s / run["rounds"] if s > 0 else None
