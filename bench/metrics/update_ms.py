"""Device milliseconds per round of the ops under the named scope
``repro/update``: the DSE-MVR update arithmetic between the gradient and
gossip calls (the x step, the MVR direction; in the communication step
x_half, h, the SGT message, the SPA ``x_ref - y``, the casts and the
``x_ref`` copy), whichever path runs it, per-leaf or fused.  A union of
their intervals, averaged over the chips; nothing where no op carries the
scope."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    s = t.scope_s("repro/update")
    return 1e3 * s / run["rounds"] if s > 0 else None
