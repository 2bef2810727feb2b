"""From optimized HLO text to a table {instruction name: scope}.

Parity: no reference counterpart — the reference reads per-op time off
`torch.profiler`'s module hierarchy; on TPU a profiler event is one HLO
instruction (`%fusion.2183`), whose name says nothing of what it holds.
What it holds is in the compiled module's text: every instruction
carries the `op_name` it was traced under (flax's module path, the
`jax.named_scope`s of models/ and trainer/train_step.py, autodiff's
`jvp(...)` / `transpose(...)` wrappers).  This parser, beside
`hlo_budget.py` (which counts collectives in the same text), turns that
text into the table a trace reducer needs to say "this fusion is the
MLP's backward".

A scope is the `op_name` normalised (`scope_of`):

- `jit(...)` frames and control-flow frames (`while`, `body`, `cond`,
  `checkpoint`, `closed_call`) are dropped, and so is the trailing primitive
  (`dot_general`, `add`): it is the op, not where it came from;
- a transform wraps the scope it was applied under: `transpose(jvp(X))`
  -> leading `bwd` and the scope `X`, `jvp(X)` -> leading `fwd` and `X`;
  a rematerialised forward (`rematted_computation`, `remat`) ->
  `recompute`;
- `h_<i>` -> `h` (`layers_<i>` -> `layers`), so the blocks share their
  scopes.

`jit(train_step)/transpose(jvp(GPT))/h_3/mlp/c_fc/dot_general` becomes
`bwd/GPT/h/mlp/c_fc`, `jit(train_step)/transpose(jvp(loss))/exp` becomes
`bwd/loss`, `jit(train_step)/optimizer/mul` becomes `optimizer`.

A fusion takes the scope of the `dot` / `convolution` it holds (the
matmul decides what a fusion costs), else the longest common prefix of
its instructions' scopes, else — where they agree on nothing — its own
`op_name`, which is its root's: what it produces.  An instruction with
no `op_name` (parameters, compiler-made copies) maps to "".

One op loses its path on the way: the TPU compiler puts kernels of its
own in place of a `lax.ragged_dot` and writes ITS name over the traced
one (`%ragged-dot-none.7`, `%ragged-dot-metadata`, op_name
`ragged-dot-none`).  Those map to the scope `ragged_dot`: what they are
is all that is left to say of them.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
# the opcode is the first lower-case word followed by "(" after the
# result shape; layouts hold T(8,128) and S(1), never a lower-case call
_OPCODE = re.compile(r"(?:^|[\s})\]])([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPER = re.compile(r"^([\w\-]+)\((.*)\)$")
_CALL_FRAMES = frozenset({"jit", "pjit"})
_BLOCK = re.compile(r"^(h|layers)_\d+$")  # models/gpt.py, models/llama.py
_CONTROL = frozenset({"while", "body", "cond", "checkpoint", "closed_call"})
_RECOMPUTE = frozenset({"rematted_computation", "remat", "remat2"})
_MATMUL = frozenset({"dot", "convolution"})
_PHASES = ("fwd", "bwd", "recompute")


def scope_of(op_name: str) -> str:
    """The normalised scope of one `op_name` (module docstring)."""
    if not op_name:
        return ""
    parts = op_name.split("/")
    phase, path = "", []
    last_is_primitive = True
    for part in parts:
        last_is_primitive = False
        # a transform wraps the scope it was applied under:
        # `transpose(jvp(loss))` is the scope `loss`, differentiated
        wrapper = ""
        m = _WRAPPER.match(part)
        while m:
            wrapper, part = m.group(1), m.group(2)
            if wrapper == "transpose" and phase != "recompute":
                phase = "bwd"
            elif wrapper == "jvp":
                phase = phase or "fwd"
            m = _WRAPPER.match(part)
        if wrapper in _CALL_FRAMES or not part or part in _CONTROL:
            continue  # jit(f): f is a function's name, not a scope
        if part in _RECOMPUTE:
            phase = "recompute"
            continue
        block = _BLOCK.match(part)
        path.append(block.group(1) if block else part)
        last_is_primitive = not wrapper
    if path and last_is_primitive:
        path.pop()  # the primitive itself
    return "/".join(([phase] if phase else []) + path)


def _common_scope(scopes: List[str]) -> str:
    """Longest common prefix of the paths; the phase survives only where
    all agree (a fusion of the MLP's forward and backward is still the
    MLP's)."""
    phases, paths = set(), []
    for scope in scopes:
        parts = scope.split("/")
        phase = parts[0] if parts[0] in _PHASES else ""
        phases.add(phase)
        paths.append(parts[1:] if phase else parts)
    out = []
    for level in zip(*paths):
        if any(p != level[0] for p in level):
            break
        out.append(level[0])
    phase = phases.pop() if len(phases) == 1 else ""
    return "/".join(([phase] if phase else []) + [p for p in out if p])


def parse_computations(hlo_text: str) -> Dict[str, List[dict]]:
    """{computation: [{"name", "opcode", "op_name", "calls"}, ...]}."""
    comps: Dict[str, List[dict]] = {}
    current: Optional[List[dict]] = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(rest)
        name = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        current.append({
            "name": m.group(1),
            "opcode": op.group(1) if op else "",
            "op_name": name.group(1).replace("\\'", "'") if name else "",
            "calls": calls.group(1) if calls else "",
        })
    return comps


def scope_table(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} for every instruction of the module
    that can run as a device op (those inside a fused computation run as
    their fusion and are left out)."""
    comps = parse_computations(hlo_text)
    fused = {ins["calls"] for body in comps.values() for ins in body
             if ins["opcode"] == "fusion" and ins["calls"]}
    table: Dict[str, str] = {}
    for cname, body in comps.items():
        if cname in fused:
            continue
        for ins in body:
            scope = scope_of(ins["op_name"])
            if ins["opcode"] == "fusion":
                inner = comps.get(ins["calls"], [])
                matmul = [scope_of(i["op_name"]) for i in inner
                          if i["opcode"] in _MATMUL and i["op_name"]]
                named = matmul or [scope_of(i["op_name"]) for i in inner
                                   if i["op_name"]]
                common = _common_scope(named) if named else ""
                if common not in _PHASES and common:
                    scope = common
                # else its instructions agree on nothing (one stray
                # constant from another scope is enough): the fusion's
                # own metadata stands, which is its root's — what it
                # produces
            if not scope and ins["name"].startswith("ragged-dot"):
                scope = "ragged_dot"
            table[ins["name"]] = scope
    return table


# opcodes that move data and compute nothing; a fusion of nothing else
# (beside the parameters, constants and bitcasts every fusion holds) is
# a re-layout too
_MOVES = frozenset({"copy", "transpose", "reshape", "slice", "concatenate",
                    "pad", "dynamic-slice", "dynamic-update-slice"})
_FREE = frozenset({"parameter", "constant", "bitcast", "tuple",
                   "get-tuple-element", "broadcast", "iota"})


def relayouts(hlo_text: str, under: str, outside=()) -> Dict[str, str]:
    """{instruction name: opcode} of the device ops whose scope holds the
    component `under`, holds none of `outside`, and that only MOVE data:
    a `copy`, `transpose` or materialised `reshape` of the module, or a
    fusion of nothing but such ops — the compiled program's own count of
    the splits, cuts to heads, transposes and joins around a kernel.
    Kernels (`custom-call`) are never among them."""
    comps = parse_computations(hlo_text)
    bodies = {name: {i["opcode"] for i in body} - _FREE
              for name, body in comps.items()}
    opcodes = {i["name"]: i for body in comps.values() for i in body}
    found: Dict[str, str] = {}
    for name, scope in scope_table(hlo_text).items():
        parts = scope.split("/")
        if under not in parts or any(o in parts for o in outside):
            continue
        ins = opcodes[name]
        moved = bodies.get(ins["calls"], {""}) if ins["opcode"] == "fusion" \
            else {ins["opcode"]}
        if moved and moved <= _MOVES:
            found[name] = ins["opcode"]
    return found


def instructions_of(hlo_text: str, opcode: str, under: str
                    ) -> Dict[str, str]:
    """{instruction name: result shape} of every instruction of one
    `opcode` — a device op of its own or one inside a fusion — whose own
    scope holds the path `under` (`"moe/combine"`; `""`: anywhere): the
    compiled program's own count of, say, the scatters a layer still
    makes.  The shape is the text before the opcode
    (`bf16[163840,2048]{1,0:...}`; a scatter's result has its operand's
    shape)."""
    found: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(rest)
        if not op or op.group(1) != opcode:
            continue
        name = _OP_NAME.search(rest)
        scope = scope_of(name.group(1).replace("\\'", "'")) if name else ""
        if not under or f"/{under}/" in f"/{scope}/":
            found[m.group(1)] = rest[:op.start(1)].strip()
    return found
