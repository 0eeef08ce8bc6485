"""Model FLOPs of the round step's runs in the traced window
(``bench/flops.py``) over their device time, from the trace's program
events, and over the device's bf16 peak, in percent: the share of the
chips' peak that the step reaches while it runs.  Host time between
rounds is ``device_idle_share``'s, not this metric's."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    runs, seconds = t.program_runs(run["program"])
    if not runs or seconds <= 0:
        return None
    # every chip runs each round's program once, on its share of the FLOPs
    flops = runs * run["round_flops"] / run["chips"]
    return 100.0 * flops / (seconds * run["peak"]["bf16_flops"])
