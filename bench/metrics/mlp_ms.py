"""Device milliseconds per round of the ops under the named scope
``repro/mlp``: every block's feed-forward layer in the forward, the
backward and the recomputed forward.  A union of their intervals, averaged
over the chips; nothing where no op carries the scope."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    s = t.scope_s("repro/mlp")
    return 1e3 * s / run["rounds"] if s > 0 else None
