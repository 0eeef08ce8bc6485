"""Share of the traced window in which no operation ran on a device
(1 - busy union / window), averaged over the chips, in percent."""


def read(run):
    t = run["trace"]
    if t is None or not t.ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
