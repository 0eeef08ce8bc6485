"""Device milliseconds per round of the ops under the named scope
``repro/attn``: every block's attention (projections, RoPE, scores and
context, output projection) in the forward, the backward and the
recomputed forward.  A union of their intervals, averaged over the chips;
nothing where no op carries the scope."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    s = t.scope_s("repro/attn")
    return 1e3 * s / run["rounds"] if s > 0 else None
