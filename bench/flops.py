"""Model FLOPs of a training step, counted from the configuration.

Counts the multiply-adds that the forward pass requires per token, times 2
FLOPs each, times 3 for forward and backward. Recomputation (remat) is not
counted, and causal mixing is counted at half: the masked half of a score
matrix is not work the model requires. XLA's ``cost_analysis`` is not used:
it counts the body of a scanned layer once.

Per token and layer, in multiply-adds:

- attention: the q, k, v and output projections, then (S + 1) / 2 keys on
  average for q.k and again for p.v, for each of the heads;
- swiglu MLP: three d x d_ff matrices (two for the other activations);
- Mamba-2: the input projection d -> (2 di + 2 n + h), the depthwise causal
  convolution (K taps on di + 2 n channels), the output projection di -> d,
  and the SSD scan with chunk length Q: (Q + 1) / 2 positions for C.B and
  for applying it to the h x p inputs within a chunk, h p n to write each
  token into the chunk state, and h p n to read the state back out;

and the output head, d x vocab, once per token.
"""
from __future__ import annotations

from typing import Dict


def _attention_macs(m: Dict, seq: int) -> float:
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    proj = d * (h + 2 * kv) * hd + h * hd * d
    mix = 2 * h * hd * (seq + 1) / 2
    return proj + mix


def _mlp_macs(m: Dict) -> float:
    mult = 3 if m.get("activation", "swiglu") == "swiglu" else 2
    return mult * m["d_model"] * m.get("d_ff", 0)


def _mamba2_macs(m: Dict) -> float:
    d = m["d_model"]
    di = m.get("ssm_expand", 2) * d
    n, p = m["ssm_state"], m.get("ssm_head_dim", 64)
    h = di // p
    q, k = m.get("ssm_chunk", 128), m.get("ssm_conv", 4)
    proj = d * (2 * di + 2 * n + h) + di * d
    conv = k * (di + 2 * n)
    ssd = (q + 1) / 2 * n + (q + 1) / 2 * h * p + 2 * h * p * n
    return proj + conv + ssd


def macs_per_token(model: Dict, seq: int) -> float:
    """Forward multiply-adds per token of a decoder-only model."""
    kind = model["arch_type"]
    if kind not in ("dense", "ssm"):
        raise ValueError(f"no FLOP count for arch_type {kind!r}")
    layer = _mamba2_macs(model) if kind == "ssm" else \
        _attention_macs(model, seq) + _mlp_macs(model)
    return model["num_layers"] * layer + model["d_model"] * model["vocab_size"]


def train_flops_per_step(model: Dict, global_batch: int, seq: int) -> float:
    """Model FLOPs of one training step over the whole global batch."""
    return 6.0 * macs_per_token(model, seq) * global_batch * seq
