"""Model FLOPs of a training round, counted from a configuration's shapes,
and the chip peaks they are divided by.

The cell's architecture module counts a gradient pass (forward and
backward) per sequence position (its ``pass_flops_per_position``), by these
rules:

* 6 x every weight that enters a matmul over all positions: the blocks'
  attention and MLP projections and the head.  The embedding gather is
  not a matmul.
* causal attention, 3 x layers x heads x (q/k head width + v head width)
  x seq: the score and context products over the causal half (in the
  forward pass 2 x S^2/2 x heads x width for each of the two products per
  layer and sequence; backward twice that).  With one head width it is
  6 x layers x heads x head_dim x seq.

Recomputation (``remat="block"``) is not counted, nor the update arithmetic.
DSE-MVR takes two gradient passes in each of its tau-1 local steps and one
in the communication step, so a round is ``2 (tau - 1) + 1`` passes over
one local step's batch.
"""
from __future__ import annotations

import json
import pathlib

__all__ = ["passes_per_round", "positions_per_round", "round_flops", "peaks"]

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def passes_per_round(traffic: dict) -> int:
    if traffic["algorithm"] != "dse_mvr":
        raise ValueError(f"gradient passes of {traffic['algorithm']} are not counted")
    return 2 * (traffic["tau"] - 1) + 1


def positions_per_round(traffic: dict, nodes: int) -> int:
    """Sequence positions that one round consumes over all nodes."""
    return traffic["tau"] * traffic["node_batch"] * traffic["seq_len"] * nodes


def round_flops(arch, m: dict, traffic: dict, nodes: int) -> float:
    """Model FLOPs of one round over all nodes, of configuration ``m`` as
    architecture module ``arch`` counts a pass."""
    seq = traffic["seq_len"]
    per_pass = traffic["node_batch"] * seq * nodes * arch.pass_flops_per_position(m, seq)
    return passes_per_round(traffic) * per_pass


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name} "
                       f"(have {sorted(table)})")
    return table[device_kind]
