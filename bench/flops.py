"""Model FLOPs of a training round, counted from a configuration's shapes,
and the chip peaks they are divided by.

A gradient pass (forward and backward) costs, per sequence position:

* 6 x every weight that enters a matmul over all positions: the blocks'
  attention and MLP projections and the head.  The embedding gather is
  not a matmul.
* causal attention, 6 x layers x (heads x head_dim) x seq: the score and
  context products over the causal half (the forward pass is
  2 x 2 x S^2/2 x heads x head_dim per layer and sequence; backward twice
  that).

Recomputation (``remat="block"``) is not counted, nor the update arithmetic.
DSE-MVR takes two gradient passes in each of its tau-1 local steps and one
in the communication step, so a round is ``2 (tau - 1) + 1`` passes over
one local step's batch.
"""
from __future__ import annotations

import json
import pathlib

__all__ = ["matmul_params", "pass_flops_per_position", "round_flops", "peaks"]

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def matmul_params(m: dict) -> int:
    """Weights that enter a matmul at every position (blocks and head)."""
    d, h, k, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    if m["activation"] not in ("silu", "gelu"):
        raise ValueError(f"ungated MLP ({m['activation']}) not counted")
    attn = d * hd * (2 * h + 2 * k)
    mlp = 3 * d * f
    return m["n_layers"] * (attn + mlp) + d * m["vocab_size"]


def pass_flops_per_position(m: dict, seq: int) -> float:
    """Forward + backward FLOPs of one gradient pass, averaged over the
    ``seq`` positions of a sequence."""
    attn = 6 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq
    return 6 * matmul_params(m) + attn


def passes_per_round(traffic: dict) -> int:
    if traffic["algorithm"] != "dse_mvr":
        raise ValueError(f"gradient passes of {traffic['algorithm']} are not counted")
    return 2 * (traffic["tau"] - 1) + 1


def positions_per_round(traffic: dict, nodes: int) -> int:
    """Sequence positions that one round consumes over all nodes."""
    return traffic["tau"] * traffic["node_batch"] * traffic["seq_len"] * nodes


def round_flops(m: dict, traffic: dict, nodes: int) -> float:
    """Model FLOPs of one round over all nodes."""
    seq = traffic["seq_len"]
    per_pass = traffic["node_batch"] * seq * nodes * pass_flops_per_position(m, seq)
    return passes_per_round(traffic) * per_pass


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name} "
                       f"(have {sorted(table)})")
    return table[device_kind]
