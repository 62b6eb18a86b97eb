"""What decides ``correct``: the program's first training steps against a
plain float32 reference that follows the same steps.

The cell's set-up drives the Trainer through its first three steps, with
the window's own call and feed, on rows that all differ (``data.stream_seed``
gives each call of the trainer its own stream). From those steps the
program's readings are: each step's loss; the first gradient as the
optimizer got it, per leaf, worked out from Adam's first moment after one
step (m = (1 - b1) g c, with c the clipping factor that the step's gradient
norm sets); and each leaf's change after three steps, as the fourth step
receives the parameters.

The reference makes the same weights from the seed (``bench.reference``),
reads the same tokens (``bench.data``), and takes the same three AdamW
steps: gradients of the mean loss over the global batch, summed row by
row in float32 at the highest matmul precision, and an update
written out from the optimizer's published rule. It keeps each parameter
in the type the configuration serves it in. It runs once the program's
state is freed, on one chip.

Because the reference sums the gradient one row at a time, a family's
``loss`` has to be a mean of per-row terms: the loss of the batch is then
the mean of each row's loss, whatever rows it is computed with. An expert
layer that routes with capacity drops, or a load-balance loss taken over
the whole batch, makes one row's loss depend on the other rows and cannot
be compared. An auxiliary term taken per sequence can.

Three numbers are compared, each a relative gap:

- ``loss_gap``: the largest |program - reference| / reference over the
  three losses;
- ``grad_gap``: over the leaves, the largest gap between the program's norm
  of the first gradient and the reference's, over the reference's norm of
  that leaf or of the median leaf, whichever is larger;
- ``change_gap``: the same for the norm of each leaf's change over three
  steps, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (they move by round-off alone).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench.reference import family
from bench.reference.numerics import products, rounded

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
CHECK_CALLS = (1, 2)        # trainer steps of each call in the set-up
QUIET_GRAD = 1e-3           # of the median leaf's gradient norm


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits, passed as data so that a
    new seed compiles nothing."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def check_steps():
    """(call, step within the call) of each compared step, in order."""
    return [(c, s) for c, n in enumerate(CHECK_CALLS) for s in range(n)]


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(tree)]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def norms(tree) -> Dict[str, float]:
    return dict(zip(leaf_names(tree),
                    (float(v) for v in jax.device_get(leaf_norms(tree)))))


@dataclass
class Readings:
    losses: List[float]
    grad: Dict[str, float]          # per leaf, norm of the first gradient
    change: Dict[str, float]        # per leaf, norm of the change


def gaps(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The compared numbers, and the leaf that set each of the two leaf
    gaps (under ``<number>_leaf``)."""
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(prog.losses, ref.losses))}
    med_g = float(np.median(list(ref.grad.values())))
    kept = [k for k in ref.grad if ref.grad[k] >= QUIET_GRAD * med_g]
    med_c = float(np.median([ref.change[k] for k in kept]))
    for name, p, r, keys, med in (
            ("grad_gap", prog.grad, ref.grad, list(ref.grad), med_g),
            ("change_gap", prog.change, ref.change, kept, med_c)):
        worst = max(keys, key=lambda k: abs(p[k] - r[k]) / max(r[k], med))
        out[name] = abs(p[worst] - r[worst]) / max(r[worst], med)
        out[name + "_leaf"] = worst
    return out


def verdict(values: Dict[str, float], limits: Dict[str, Optional[float]]
            ) -> bool:
    """Every number that has a limit is finite and within it. A cell's
    limits file gives ``null`` for a number it does not compare (one whose
    sound readings no fault or control exceeds enough to set a limit)."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in NUMBERS if limits[k] is not None)


# ------------------------------------------------------------- reference
def _adamw(opt: Dict, step: int, decay: bool, stored: str, scale,
           g, m, v, p):
    """One AdamW update of one leaf, as the optimizer's rule reads: moments
    in float32, bias-corrected, weight decay on leaves of two or more
    dimensions, the result rounded to the type the leaf is stored in."""
    g = g * scale
    m = opt["b1"] * m + (1 - opt["b1"]) * g
    v = opt["b2"] * v + (1 - opt["b2"]) * g * g
    delta = (m / (1 - opt["b1"] ** step)) / (
        jnp.sqrt(v / (1 - opt["b2"] ** step)) + opt["eps"])
    if decay and opt["weight_decay"] > 0:
        delta = delta + opt["weight_decay"] * p
    return rounded(p - opt["lr"] * delta, stored), m, v


class Reference:
    """The reference of one cell, compiled once per process.

    ``precision`` float32 is the reference. Another precision gives the
    control: the configuration's bfloat16 replaced by it, in the weights as
    stored and in the operands of every product."""

    def __init__(self, model: Dict, workload: Dict, family_name: str,
                 precision: str = "float32"):
        self.model, self.wl = model, workload
        self.fam = family(family_name)
        low = model.get("dtype", "bfloat16")
        self.stored = jax.tree.map(
            lambda t: precision if precision != "float32" and t == low else t,
            self.fam.served_dtypes(model))
        mm = products(precision)
        fam, stored = self.fam, self.stored

        def block(acc, params, tokens, labels, weight):
            loss, g = jax.value_and_grad(fam.loss)(params, tokens, labels,
                                                   model, mm)
            return loss, jax.tree.map(lambda a, b: a + weight * b, acc, g)

        self._init = jax.jit(lambda key: jax.tree.map(
            rounded, fam.init(model, key), stored))
        self._block = jax.jit(block, donate_argnums=0)
        self._update = jax.jit(partial(_adamw, workload["optimizer"]),
                               static_argnums=(1, 2))
        self._zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self._gnorm = jax.jit(lambda t: jnp.sqrt(sum(
            jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t))))

    def init(self, seed: int):
        return self._init(seed_key(seed))

    def _gradient(self, params, tokens, labels, rows):
        """Mean loss over ``rows`` of the batch, and its gradient, one row
        at a time."""
        acc = self._zeros(params)
        total = 0.0
        w = 1.0 / (rows[1] - rows[0])
        for i in range(rows[0], rows[1]):
            loss, acc = self._block(acc, params, tokens[i:i + 1],
                                    labels[i:i + 1], jnp.float32(w))
            total += w * float(loss)
        return total, acc

    def run(self, seed: int, fault: Optional[str] = None) -> Readings:
        """The readings of the reference's three steps.

        ``fault`` plants a fault in the reference, for the calibration of
        the limits: ``half`` takes the mean over the first half of the
        batch only; ``local`` leaves out the exchange between chips, so
        each gradient is the first chip's mean over its own rows, divided
        by the number of chips."""
        wl, opt = self.wl, self.wl["optimizer"]
        B, S, V = wl["global_batch"], wl["seq_len"], self.model["vocab_size"]
        dp = wl.get("data_parallel", 1)
        rows = {None: (0, B), "half": (0, B // 2), "local": (0, B // dp)}[fault]
        params = self.init(seed)
        treedef = jax.tree.structure(params)
        stored = jax.tree.leaves(self.stored)
        moments = None
        losses, grad = [], {}
        for k, (call, step) in enumerate(check_steps()):
            tokens, labels = data.batch(V, B, S, data.stream_seed(seed, call),
                                        step)
            loss, g = self._gradient(params, jnp.asarray(tokens),
                                     jnp.asarray(labels), rows)
            if fault == "local":
                g = jax.tree.map(lambda x: x / dp, g)
                loss = self._gradient(params, jnp.asarray(tokens),
                                      jnp.asarray(labels), (0, B))[0] \
                    if dp > 1 else loss
            losses.append(loss)
            gnorm = float(self._gnorm(g))
            if k == 0:
                grad = norms(g)
            clip = opt["grad_clip"]
            scale = jnp.float32(min(1.0, clip / max(gnorm, 1e-9))
                                if clip > 0 else 1.0)
            leaves = jax.tree.leaves(g)
            if moments is None:
                moments = [(np.zeros(x.shape, np.float32),) * 2
                           for x in leaves]
            flat_p = jax.tree.leaves(params)
            new_p = []
            for i, (gi, pi) in enumerate(zip(leaves, flat_p)):
                m, v = moments[i]
                pi, m, v = self._update(jnp.float32(k + 1), pi.ndim >= 2,
                                        stored[i], scale, gi, jnp.asarray(m),
                                        jnp.asarray(v), pi)
                moments[i] = (np.asarray(m), np.asarray(v))
                new_p.append(pi)
            del g, leaves
            params = jax.tree.unflatten(treedef, new_p)
        change = norms(jax.tree.map(lambda a, b: a - b, params,
                                    self.init(seed)))
        return Readings(losses, grad, change)
