"""Model FLOPs of a training step, counted from the configuration.

Counts the multiply-adds that the forward pass requires per token, times 2
FLOPs each, times 3 for forward and backward. Recomputation (remat) is not
counted, and causal mixing is counted at half: the masked half of a score
matrix is not work the model requires. XLA's ``cost_analysis`` is not used:
it counts the body of a scanned layer once.

The multiply-adds per token are the model family's own count:
``macs_per_token(model, seq)`` of the reference module that the
configuration file names under ``reference`` (``bench/reference/``), for
every layer and the output head. A family that gives no count is an error.

An expert layer counts what this chip computes of it, so that ``mfu``
cannot read above the chip's peak: the router over all E experts and the
shared experts in full; for the routed experts, top_k x (experts held
here) / E expert evaluations per token, this chip's share where each
expert layer is divided over chips; the absent experts cost nothing here.
"""
from __future__ import annotations

from typing import Dict

from bench.reference import family


def macs_per_token(config: Dict, seq: int) -> float:
    """Forward multiply-adds per token of the configuration's model."""
    name = config["reference"]
    count = getattr(family(name), "macs_per_token", None)
    if count is None:
        raise ValueError(f"reference family {name!r} gives no "
                         f"macs_per_token: no FLOP count")
    return count(config["model"], seq)


def train_flops_per_step(config: Dict, global_batch: int, seq: int) -> float:
    """Model FLOPs of one training step over the whole global batch."""
    return 6.0 * macs_per_token(config, seq) * global_batch * seq
