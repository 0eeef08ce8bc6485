"""Sequence positions of all nodes' batches consumed in the window, over the
whole window (host clock, from the first round's start to the last round's
loss on the host), per chip."""


def read(run):
    return run["rounds"] * run["positions_per_round"] / run["window_s"] / run["chips"]
