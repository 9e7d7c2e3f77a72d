"""Device self time per program layer: the join of a device trace to the
program's layer scopes, through the compiled step's HLO text.

A device event names the HLO instruction it ran (``%fusion.494 = ...``)
and carries no op name. The compiled module's text gives each
instruction its ``metadata={op_name="..."}``, the JAX name stack of the
code it came from: ``jit(train_step)/transpose(jvp())/while/body/
closed_call/checkpoint/attention/dot_general``. A path component may be
wrapped by a transformation (``jvp(embed)``, ``transpose(jvp(head))``);
unwrapped, the last component that is one of the program's
``LAYER_SCOPES`` is the op's layer. The op ran the backward pass where
the path holds ``transpose(`` and no ``rematted_computation``; otherwise
it ran forward, remat's recompute included.

XLA adds work that no scope names: fusions it builds without metadata,
layout copies, asynchronous copies and slices, the layer scan's slicing
of the stacked weights. A fusion without metadata takes the layer that
most instructions of its fused computation carry. An op that only moves
data and still has no layer is charged to the first layer that reads
what it moved, following the data through other such ops, or, where no
layer reads it, to the last layer that wrote it. What is left (loop
control, and ops the text does not name) is unscoped.

The text is not in the run's context: ``step_text`` builds the program
again from the cell and compiles its step on the same shapes.

Time is self time (``bench.trace.reduce``): an op's duration less the
ops nested in it, summed over the ops that lie wholly inside the traced
window, per traced step (one ``dispatch`` span each), mean over chips.
"""
from __future__ import annotations

import re
from collections import Counter, defaultdict, deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Key = Optional[Tuple[str, str]]          # (layer, "fwd" | "bwd"), or None

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (?:\(.*?\)|\S+) "
                    r"([\w\-]+)\(")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_OP_NAME = re.compile(r"\bmetadata=\{[^{}]*?op_name=\"([^\"]*)\"")
_EVENT = re.compile(r"^%?([\w.\-]+) = ")
_WRAP = re.compile(r"^[\w\-]+\((.*)\)$")
_KERNEL = 'custom_call_target="tpu_custom_call"'

# opcodes that only move or re-lay data; a fusion moves data when its
# fused computation holds nothing else
MOVES_DATA = frozenset((
    "parameter", "constant", "bitcast", "reshape", "copy", "copy-start",
    "copy-done", "async-start", "async-done", "transpose", "broadcast",
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "tuple", "get-tuple-element", "custom-call"))


def layer_scopes() -> Optional[Tuple[str, ...]]:
    """The program's layer scopes; None where the program has none."""
    try:
        from repro.obs import LAYER_SCOPES
    except ImportError:
        return None
    return tuple(LAYER_SCOPES)


def layer_of(op_name: str, scopes: Iterable[str]) -> Key:
    """(layer, direction) of an op's name stack, None outside every scope."""
    scopes = set(scopes)
    layer = None
    for comp in op_name.split("/"):
        while (m := _WRAP.match(comp)):
            comp = m.group(1)
        if comp in scopes:
            layer = comp
    if layer is None:
        return None
    bwd = "transpose(" in op_name and "rematted_computation" not in op_name
    return layer, "bwd" if bwd else "fwd"


def _operands(line: str, start: int) -> List[str]:
    depth, j = 1, start
    while j < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        j += 1
    return re.findall(r"%([\w.\-]+)", line[start:j - 1])


def _nearest(start: str, step: Callable[[str], List[str]],
             keys: Dict[str, Key], moves: Callable[[str], bool]) -> Key:
    """The key of the first scoped instruction reached from ``start`` by
    ``step``, passing only through instructions that move data."""
    seen, todo = {start}, deque(step(start))
    while todo:
        name = todo.popleft()
        if name in seen:
            continue
        seen.add(name)
        if keys.get(name) is not None:
            return keys[name]
        if moves(name):
            todo.extend(step(name))
    return None


def layer_keys(hlo_text: str, scopes: Iterable[str]) -> Dict[str, Key]:
    """{instruction name: (layer, direction)} over a module's text."""
    scopes = tuple(scopes)
    keys: Dict[str, Key] = {}
    members: Dict[Optional[str], List[Key]] = defaultdict(list)
    opcodes: Dict[Optional[str], set] = defaultdict(set)
    calls: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        # a Pallas kernel computes, whatever its opcode says
        opcode[name] = "kernel" if _KERNEL in line else m.group(2)
        opcodes[comp].add(opcode[name])
        operands[name] = _operands(line, m.end())
        if (n := _OP_NAME.search(line)):
            keys[name] = layer_of(n.group(1), scopes)
            members[comp].append(keys[name])
        if (c := _CALLS.search(line)):
            calls[name] = c.group(1)
    for name, callee in calls.items():
        if keys.get(name) is None:
            votes = Counter(k for k in members.get(callee, ()) if k)
            if votes:
                keys[name] = votes.most_common(1)[0][0]

    users: Dict[str, List[str]] = defaultdict(list)
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)

    def moves(name: str) -> bool:
        if opcode.get(name) == "fusion":
            return opcodes.get(calls.get(name), {"?"}) <= MOVES_DATA
        return opcode.get(name) in MOVES_DATA

    found = {}
    for name in opcode:
        if keys.get(name) is None and moves(name):
            found[name] = (
                _nearest(name, lambda x: users.get(x, []), keys, moves)
                or _nearest(name, lambda x: operands.get(x, []), keys,
                            moves))
    keys.update((k, v) for k, v in found.items() if v is not None)
    return keys


def layer_ms(trace, hlo_text: str, scopes: Iterable[str]) -> Dict[Key, float]:
    """Milliseconds of device self time per (layer, direction) per traced
    step; the key None holds what no scope claims."""
    keys = layer_keys(hlo_text, scopes)
    lo, hi = trace.window()
    steps = sum(h[0] == "dispatch" for h in trace.host)
    tot: Dict[Key, float] = {}
    for ops in trace.devices.values():
        for o in ops:
            if o.start >= lo and o.end <= hi:
                m = _EVENT.match(o.text)
                key = keys.get(m.group(1)) if m else None
                tot[key] = tot.get(key, 0.0) + o.self_ns
    n = len(trace.devices) * max(steps, 1)
    return {k: v / n / 1e6 for k, v in tot.items()}


def compiled_text(prog, state, batch) -> str:
    """The HLO text of ``prog``'s step compiled for ``state`` and
    ``batch``, arrays or their shapes."""
    import jax
    with jax.set_mesh(prog.mesh):
        return prog.step.lower(state, batch).compile().as_text()


_TEXTS: Dict[Tuple[str, int, int], str] = {}


def step_text(cell, chips: int) -> str:
    """The compiled text of the step a run of ``cell`` traced.

    The program is built again from the cell as the harness builds it and
    its step compiled on the shapes of the state and the batch. The same
    module compiles to the same instruction names, so each event of the
    trace finds its own; with the harness's persistent cache the compile
    is a cache load. One compile serves every reader of the run."""
    import json

    import jax
    import jax.numpy as jnp
    from bench import program
    from bench.reference import weights as W

    cfg, seq = cell.config, cell.traffic["seq_len"]
    key = (json.dumps(cfg, sort_keys=True), seq, chips)
    if key not in _TEXTS:
        prog = program.build(cfg, seq, jax.devices()[:chips])
        seed = W.seed_key(0)
        state = jax.eval_shape(prog.init, seed,
                               jax.eval_shape(prog.weights, seed))
        batch = {"tokens": jax.ShapeDtypeStruct(
            (cfg["global_batch"], seq), jnp.int32)}
        _TEXTS[key] = compiled_text(prog, state, batch)
    return _TEXTS[key]


def read(ctx, keys) -> Optional[float]:
    """Summed ms per step of ``keys`` in the run's traced window; None
    where the run has no trace or the program has no scopes."""
    scopes = layer_scopes()
    if scopes is None or ctx.trace is None:
        return None
    ms = layer_ms(ctx.trace, step_text(ctx.cell, ctx.chips), scopes)
    return sum(ms.get(k, 0.0) for k in keys)
