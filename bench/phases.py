"""Device time of the training step's phases, by the names the program
gives them.

``repro.train.train_step`` names its parts with ``jax.named_scope``:
``forward`` (the loss), ``grad_sync`` (the explicit modes' reduction) and
``optimizer``. The compiled step's HLO gives each instruction the name
stack of the code it came from, the ``op_name`` of its metadata, such as
``jit(train_step)/shard_map/transpose(jvp(forward))/while/body/...``, and
an op of the device trace is named by its instruction (``fusion.582``).
So an op belongs to

- ``forward``: under ``forward`` and not under a ``transpose(``;
- ``backward``: under ``forward`` and a ``transpose(``, so
  ``transpose(jvp(forward))``;
- ``recompute``: a backward op under remat's ``rematted_computation``,
  counted in ``backward`` too;
- ``optimizer``, ``grad_sync``: under that scope.

An op without such a scope takes the phases of the op whose computation
holds it (the ``while`` of a scanned layer, say). One whose name is not in
the step's HLO, or that still has no phase (copies that XLA adds without
metadata at the top level, the metrics' ``pmean``), is ``unattributed``.
Only ops that start inside a step program count. A phase's time on a chip
is the length of the union of its ops' intervals, per step; the metrics
average it over chips.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench import hlo, trace

UNATTRIBUTED = "unattributed"

# an instruction line: its name, then its opcode (the first word directly
# followed by "(" after " = "), then the op_name of its metadata, if any
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?(\S+) = .*?\s([a-z][\w-]*)\('
                    r'(?:.*?\bmetadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)")?')
# the computations an instruction calls: a while's body, a fusion's, ...
_CALLS = re.compile(r"\b(?:body|condition|calls|to_apply|branch_computations)"
                    r"=\{?((?:%[\w.-]+(?:, )?)+)")
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")


@dataclass(frozen=True)
class Instr:
    phases: Tuple[str, ...]
    opcode: str


def phases_of(op_name: str) -> Tuple[str, ...]:
    """The phases an op with this name stack belongs to."""
    scopes = {_WRAPPERS.sub("", c) for c in op_name.split("/")}
    for p in ("optimizer", "grad_sync"):
        if p in scopes:
            return (p,)
    if "forward" not in scopes:
        return ()
    if "transpose(" not in op_name:
        return ("forward",)
    if "rematted_computation" in scopes:
        return ("backward", "recompute")
    return ("backward",)


def instructions(hlo_text: str) -> Dict[str, Instr]:
    """Each instruction of ``hlo_text`` by name, with its phases and its
    opcode. An instruction whose name stack names no phase takes the
    phases of the instruction that calls its computation, as the ops of a
    scanned layer's body take those of its ``while``: XLA drops or
    rewrites the metadata of some ops it makes, such as the windowed sums
    that mamba2's ``cumsum`` becomes on a TPU."""
    own: Dict[str, Tuple[str, ...]] = {}
    opcode: Dict[str, str] = {}
    home: Dict[str, str] = {}        # instruction -> its computation
    caller: Dict[str, str] = {}      # computation -> an instruction calling it
    comp = ""
    for line in hlo_text.splitlines():
        if line[:1].strip() and line.rstrip().endswith("{"):
            comp = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        own[name], opcode[name], home[name] = (
            phases_of(m.group(3) or ""), m.group(2), comp)
        for called in _CALLS.findall(line):
            for c in re.findall(r"%([\w.-]+)", called):
                caller.setdefault(c, name)
    out = {}
    for name in own:
        at = name          # computations call down a tree: this ends
        while not own[at] and home[at] in caller:
            at = caller[home[at]]
        out[name] = Instr(own[at], opcode[name])
    return out


@dataclass
class Reduction:
    """Per step and chip, averaged over chips: each phase's time in ns
    (``UNATTRIBUTED`` included) and its collective ops. A phase none of
    whose ops ran is missing from both."""
    ns: Dict[str, float]
    collectives: Dict[str, float]


def _step_ops(w: trace.DeviceWindow) -> List[trace.Event]:
    """The window's innermost ops that start inside a step program."""
    starts = [s.start for s in w.steps]
    out = []
    for e in w.ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < w.steps[i].end:
            out.append(e)
    return out


def reduce(windows: Dict[int, trace.DeviceWindow],
           instrs: Dict[str, Instr]) -> Reduction:
    """The phases of every chip's window, by the step's instructions."""
    ns: Dict[str, float] = {}
    coll: Dict[str, float] = {}
    for w in windows.values():
        spans: Dict[str, List[trace.Interval]] = {}
        count: Dict[str, int] = {}
        for e in _step_ops(w):
            ins = instrs.get(e.name)
            # a collective counts once: a synchronous one, or the -done of
            # an asynchronous one (as bench.hlo counts them)
            collective = ins is not None and (
                ins.opcode.removesuffix("-done") in hlo.COLLECTIVES)
            for p in (ins.phases if ins else ()) or (UNATTRIBUTED,):
                spans.setdefault(p, []).append((e.start, e.end))
                if collective:
                    count[p] = count.get(p, 0) + 1
        for p, n in count.items():
            coll[p] = coll.get(p, 0.0) + n / len(w.steps)
        for p, iv in spans.items():
            length = sum(b - a for a, b in trace.union(iv))
            ns[p] = ns.get(p, 0.0) + length / len(w.steps)
    k = len(windows)
    return Reduction({p: v / k for p, v in ns.items()},
                     {p: v / k for p, v in coll.items()})


def of(run) -> Optional[Reduction]:
    """The phases of a traced run (``bench.harness.RunRecord``), from its
    trace and its step's HLO, which is compiled and parsed on the first
    call only and kept on the run; None for a run without them."""
    if run.step_hlo is None or not run.windows:
        return None
    if getattr(run, "_phases", None) is None:
        run._phases = reduce(run.windows, instructions(run.step_hlo()))
    return run._phases


def phase_ms(run, phase: str) -> Optional[float]:
    """``phase``'s time per step in ms, averaged over chips; None where no
    op of it ran."""
    r = of(run)
    if r is None or phase not in r.ns:
        return None
    return r.ns[phase] / 1e6
