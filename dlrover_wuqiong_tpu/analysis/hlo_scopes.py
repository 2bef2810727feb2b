"""From optimized HLO text to a table {instruction name: owner}.

Parity: no reference counterpart — the reference reads per-op time off
`torch.profiler`'s module hierarchy; on TPU a profiler event is one HLO
instruction (`%fusion.2183`), whose name says nothing of what it holds.
What it holds is in the compiled module's text: every instruction
carries the `op_name` it was traced under (flax's module path, the
`jax.named_scope`s of models/ and trainer/train_step.py, autodiff's
`jvp(...)` / `transpose(...)` wrappers).  This parser turns that text
into the table a trace reducer needs to say "this fusion is the MLP's
backward"; `read_instruction` is the package's one reader of an HLO
line (`hlo_budget.iter_collectives` counts collectives through it).

A scope is the `op_name` normalised (`scope_of`):

- `jit(...)` frames and control-flow frames (`while`, `body`, `cond`,
  `checkpoint`, `closed_call`) are dropped, and so is the trailing primitive
  (`dot_general`, `add`): it is the op, not where it came from;
- a transform wraps the scope it was applied under: `transpose(jvp(X))`
  -> leading `bwd` and the scope `X`, `jvp(X)` -> leading `fwd` and `X`;
  a rematerialised forward (`rematted_computation`, `remat`) ->
  `recompute`; the tracer names the differentiated function once a
  transform (`transpose(jvp(GPT))/jvp(GPT)/checkpoint/...`): the repeat
  is dropped, so a module's forward, recomputed and backward
  instructions agree on one path;
- `h_<i>` -> `h` (`layers_<i>` -> `layers`), so the blocks share their
  scopes;
- several names joined by `;` (instructions the compiler merged): the
  common scope of the pieces.

`jit(train_step)/jvp(GPT)/h_3/mlp/c_fc/dot_general` becomes
`fwd/GPT/h/mlp/c_fc`, `jit(train_step)/transpose(jvp(loss))/exp` becomes
`bwd/loss`, `jit(train_step)/optimizer/mul` becomes `optimizer`.

Who owns an instruction, and how the table came to say so (`owners`,
the entry's `via`).  The model's root is whatever holds the block axis
(`GPT` in `GPT/h/...`); alone it says nothing, so it counts as no name:

- `own`: the instruction's own scope; the two halves of an async pair
  (`copy-start` / `copy-done`, `slice-*`, `all-gather-*`) share one;
- a fusion (and whatever else `calls` a computation it runs: an
  `async-start`): `matmul`, the scope of the `dot` / `convolution` it holds
  (the matmul decides what a fusion costs); else `common`, the longest
  common prefix of its instructions' scopes where that names something
  below the root and its block axis; else `root`, the fusion's own
  `op_name`, which is its root instruction's: what it produces; else
  `members`, the scope most of its instructions carry.  `members` keeps
  every scope they carried;
- no name at all (compiler-made copies, async halves, re-layouts):
  `consumer`, the scope its users agree on, as a fusion's instructions
  agree (a re-layout is made FOR whoever asked), else `producer`, its
  first operand's that has one, both through chains of such
  instructions; a parameter staged for users that disagree goes to the
  scope most of them carry; else `none`.

Beside the owner a `kind`: `move` for an opcode that moves data and
computes nothing (`_MOVES`, the async copy and slice halves) and for a
fusion of nothing else, `compute` for the rest.  `scope_table` is the
{name: scope} view of the same table.

One op loses its path on the way: the TPU compiler puts kernels of its
own in place of a `lax.ragged_dot` and writes ITS name over the traced
one (`%ragged-dot-none.7`, `%ragged-dot-metadata`, op_name
`ragged-dot-none`).  Those map to the scope `ragged_dot`: what they are
is all that is left to say of them.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Iterable, List, Optional

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
# the opcode is the first lower-case word followed by "(" after the
# result shape; layouts hold T(8,128) and S(1), never a lower-case call
_OPCODE = re.compile(r"(?:^|[\s})\]])([a-z][a-z0-9\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPER = re.compile(r"^([\w\-]+)\((.*)\)$")
_CALL_FRAMES = frozenset({"jit", "pjit"})
_BLOCK = re.compile(r"^(h|layers)_\d+$")  # models/gpt.py, models/llama.py
_BLOCK_AXES = frozenset({"h", "layers"})
_CONTROL = frozenset({"while", "body", "cond", "checkpoint", "closed_call"})
_RECOMPUTE = frozenset({"rematted_computation", "remat", "remat2"})
_MATMUL = frozenset({"dot", "convolution"})
_PHASES = ("fwd", "bwd", "recompute")


def scope_of(op_name: str) -> str:
    """The normalised scope of one `op_name` (module docstring)."""
    if ";" in op_name:
        return _common_scope([s for s in map(scope_of, op_name.split(";"))
                              if s])
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
        if wrapper and path and path[-1] == part:
            continue  # transpose(jvp(GPT))/jvp(GPT): one function, twice
        block = _BLOCK.match(part)
        path.append(block.group(1) if block else part)
        last_is_primitive = not wrapper
    if path and last_is_primitive:
        path.pop()  # the primitive itself
    return "/".join(([phase] if phase else []) + path)


def _split(scope: str):
    """(phase, path) of a scope."""
    parts = scope.split("/")
    return (parts[0], parts[1:]) if parts[0] in _PHASES else ("", parts)


def _common_scope(scopes: Iterable[str]) -> str:
    """Longest common prefix of the paths; the phase survives only where
    all agree (a fusion of the MLP's forward and backward is still the
    MLP's)."""
    phases, paths = set(), []
    for scope in scopes:
        phase, path = _split(scope)
        phases.add(phase)
        paths.append(path)
    out = []
    for level in zip(*paths):
        if any(p != level[0] for p in level):
            break
        out.append(level[0])
    phase = phases.pop() if len(phases) == 1 else ""
    out = [p for p in out if p]
    return "/".join(([phase] if phase else []) + out) if out else ""


def read_instruction(line: str) -> Optional[dict]:
    """One line of HLO text as {"name", "shape", "opcode", "operands",
    "op_name", "calls"}, or None where the line is no instruction.  The
    shape is the text before the opcode (`bf16[8,64]{1,0:T(8,128)(2,1)}`,
    a tuple's in its parentheses), the operands the `%names` inside the
    call's own parentheses."""
    m = _INSTRUCTION.match(line)
    if not m:
        return None
    rest = m.group(2)
    op = _OPCODE.search(rest)
    shape, operands = "", []
    if op:
        shape = rest[:op.start(1)].strip()
        # the call's closing parenthesis: operands printed with their
        # types hold layouts, T(8,128), of their own
        start = op.end()
        end = rest.find(")", start)
        while end != -1 and \
                rest.count("(", start, end) > rest.count(")", start, end):
            end = rest.find(")", end + 1)
        if end != -1:
            operands = _OPERAND.findall(rest, start, end)
    name = _OP_NAME.search(rest)
    calls = _CALLS.search(rest)
    return {
        "name": m.group(1),
        "shape": shape,
        "opcode": op.group(1) if op else "",
        "operands": operands,
        "op_name": name.group(1).replace("\\'", "'") if name else "",
        "calls": calls.group(1) if calls else "",
    }


def parse_computations(hlo_text: str) -> Dict[str, List[dict]]:
    """{computation: [instruction, ...]}, each as `read_instruction`
    gives it."""
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
        ins = read_instruction(line)
        if ins:
            current.append(ins)
    return comps


# opcodes that move data and compute nothing; a fusion of nothing else
# (beside the parameters, constants and bitcasts every fusion holds) is
# a re-layout too
_MOVES = frozenset({"copy", "transpose", "reshape", "slice", "concatenate",
                    "pad", "dynamic-slice", "dynamic-update-slice"})
_ASYNC_MOVES = frozenset({"copy-start", "copy-done", "slice-start",
                          "slice-done"})
_FREE = frozenset({"parameter", "constant", "bitcast", "tuple",
                   "get-tuple-element", "broadcast", "iota"})


def _names_something(scope: str, roots: frozenset) -> bool:
    """Whether a scope holds a component below the model's root and its
    block axis (`GPT/h/mlp`, `loss`; not `GPT/h`, not `GPT`)."""
    path = _split(scope)[1]
    for i, part in enumerate(path):
        if part in roots:
            return any(p not in _BLOCK_AXES for p in path[i + 1:])
    return any(path)


def _bare(scope: str, roots: frozenset) -> bool:
    """No name, or the model's root alone, which says nothing."""
    path = _split(scope)[1]
    return not path or path[-1] in roots


def _fusion_owner(inner: List[tuple], roots: frozenset, own: str) -> tuple:
    """(scope, via, members) of a fusion from the (opcode, scope) of the
    named instructions it holds and its `own` name."""
    votes = Counter(s for _, s in inner)
    members = list(votes)
    matmul = {s for op, s in inner if op in _MATMUL}
    for via, candidates in (("matmul", matmul), ("common", members)):
        common = _common_scope(candidates)
        if common and _names_something(common, roots):
            return common, via, members
    if own:
        return own, "root", members
    for scope, _ in votes.most_common():
        if not _bare(scope, roots):
            return scope, "members", members
    return "", "none", members


def _resolve(comps: Dict[str, List[dict]]) -> Dict[str, dict]:
    # a fusion, and an async op's start, run the computation they call
    called = {ins["calls"] for body in comps.values() for ins in body
              if ins["calls"]}
    top = {ins["name"]: ins for cname, body in comps.items()
           if cname not in called for ins in body}
    scopes = {name: scope_of(name) for name in {
        ins["op_name"] for body in comps.values() for ins in body}}
    roots = frozenset(path[i - 1] for path in (
        _split(s)[1] for s in set(scopes.values()))
        for i in range(1, len(path)) if path[i] in _BLOCK_AXES)
    held = {cname: ({i["opcode"] for i in comps[cname]} - _FREE,
                    [(i["opcode"], scopes[i["op_name"]]) for i in comps[cname]
                     if scopes[i["op_name"]]])
            for cname in called if cname in comps}

    def named(ins: dict) -> str:
        scope = scopes[ins["op_name"]]
        if not scope and ins["name"].startswith("ragged-dot"):
            return "ragged_dot"
        return "" if _bare(scope, roots) else scope

    table: Dict[str, dict] = {}
    users: Dict[str, List[str]] = {}
    for name, ins in top.items():
        for operand in ins["operands"]:
            users.setdefault(operand, []).append(name)
        scope, via, members = named(ins), "own", []
        if ins["calls"]:
            opcodes, inner = held.get(ins["calls"], (set(), []))
            scope, via, members = _fusion_owner(inner, roots, scope)
            moves = bool(opcodes) and opcodes <= _MOVES
        else:
            moves = ins["opcode"] in _MOVES or ins["opcode"] in _ASYNC_MOVES
        table[name] = {"scope": scope, "via": via if scope else "none",
                       "kind": "move" if moves else "compute",
                       "members": members}
    # the two halves of an async pair are one op
    for name, ins in top.items():
        if not (ins["opcode"].endswith("-done") and ins["operands"]):
            continue
        start, done = table.get(ins["operands"][0]), table[name]
        if start is None or \
                not top[ins["operands"][0]]["opcode"].endswith("-start"):
            continue
        done["kind"] = start["kind"]
        if bool(start["scope"]) != bool(done["scope"]):
            named_half = start if start["scope"] else done
            for half in (start, done):
                half["scope"], half["via"] = named_half["scope"], "own"
    # text order is a schedule: operands stand before their users
    made_from: Dict[str, str] = {}
    for name, ins in top.items():
        made_from[name] = table[name]["scope"] or next(
            (made_from[o] for o in ins["operands"] if made_from.get(o)), "")
    asked_for: Dict[str, str] = {}
    most_ask: Dict[str, str] = {}
    for name in reversed(top):
        own = table[name]["scope"]
        for asks, majority in ((asked_for, False), (most_ask, True)):
            found = [] if own else [asks[u] for u in users.get(name, ())
                                    if asks.get(u)]
            common = _common_scope(found)
            if len(set(found)) > 1 and \
                    not _names_something(common, roots):
                common = Counter(found).most_common(1)[0][0] \
                    if majority else ""
            asks[name] = own or common
    for name, entry in table.items():
        if entry["scope"]:
            continue
        for via, scope in (("consumer", asked_for[name]),
                           ("producer", made_from[name]),
                           ("consumer", most_ask[name])):
            if scope:
                entry["scope"], entry["via"] = scope, via
                break
    return table


def owners(hlo_text: str) -> Dict[str, dict]:
    """{instruction name: {"scope", "via", "kind", "members"}} for every
    instruction of the module that can run as a device op (those inside
    a fused computation run as their fusion and are left out): who owns
    it, how the table came to say so, whether it only moves data, and
    the scopes a fusion's instructions carried (module docstring)."""
    return _resolve(parse_computations(hlo_text))


def scope_table(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope}: `owners`' first column."""
    return {name: entry["scope"]
            for name, entry in owners(hlo_text).items()}


_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")
_PARAMETER = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\sparameter\((\d+)\)")
_SLICES = frozenset({"slice", "dynamic-slice"})
_HOLDS_OPS = frozenset({"while", "conditional", "call"})


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape as `read_instruction` gives it — an array's
    (`bf16[1,4,8192,3584]{3,2,1,0:T(8,128)(2,1)}`) or a tuple's, summed;
    an element's width is the number in its type's name (`pred`: a
    byte), a `token` or an opaque handle is nothing."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        bits = 8 if dtype == "pred" else int(
            (re.search(r"\d+", dtype) or [0])[0])
        count = 1
        for dim in filter(None, dims.split(",")):
            count *= int(dim)
        total += count * bits // 8
    return total


def moved_bytes(hlo_text: str, under: str) -> Dict[str, dict]:
    """{instruction name: {"scope", "read", "written"}}: the bytes of the
    operands and of the result of every device op whose owner's scope
    (`owners`) holds the path `under` (`"hc"`, `"hc/coeff"`) — the
    compiled program's own byte ledger of a layer, what its ops move
    through HBM if nothing stays in fast memory between them.  A
    fusion's operand that the fusion only SLICES counts at its slices
    (a fusion that reads one lane of the stream is handed all four);
    the two halves of an async pair count once, at what the `-done`
    half delivers, read and written; an op that only holds other ops
    (`while`, `conditional`) counts nothing and its body's ops count
    ONCE, whatever the trip count."""
    comps = parse_computations(hlo_text)
    top = {ins["name"]: ins for body in comps.values() for ins in body}
    index = {m.group(1): int(m.group(2)) for m in map(
        _PARAMETER.match, hlo_text.splitlines()) if m}

    def sliced(cname: str) -> Dict[int, int]:
        """{operand index: bytes} of the parameters a called
        computation reads only through slices."""
        body = comps.get(cname, [])
        users: Dict[str, List[dict]] = {}
        for ins in body:
            for operand in ins["operands"]:
                users.setdefault(operand, []).append(ins)
        return {index[ins["name"]]: sum(
                    shape_bytes(u["shape"]) for u in users[ins["name"]])
                for ins in body
                if ins["opcode"] == "parameter" and users.get(ins["name"])
                and all(u["opcode"] in _SLICES for u in users[ins["name"]])}

    found: Dict[str, dict] = {}
    for name, entry in _resolve(comps).items():
        ins = top[name]
        opcode = ins["opcode"]
        if f"/{under}/" not in f"/{entry['scope']}/" or opcode in _FREE \
                or opcode in _HOLDS_OPS or opcode.endswith("-start"):
            continue
        written = shape_bytes(ins["shape"])
        if opcode.endswith("-done"):
            read = written
        else:
            at_slices = sliced(ins["calls"]) if ins["calls"] else {}
            read = sum(at_slices[i] if i in at_slices
                       else shape_bytes(top[o]["shape"]) if o in top else 0
                       for i, o in enumerate(ins["operands"]))
        found[name] = {"scope": entry["scope"], "read": read,
                       "written": written}
    return found


def relayouts(hlo_text: str, under: str, outside=()) -> Dict[str, str]:
    """{instruction name: opcode} of the device ops whose scope holds the
    component `under`, holds none of `outside`, and that only MOVE data
    (`owners`' kind): a `copy`, `transpose` or materialised `reshape` of
    the module or made for it, or a fusion of nothing but such ops — the
    compiled program's own count of the splits, cuts to heads, transposes
    and joins around a kernel.  The compiler's async copies and slices
    are not among them (they stage an operand in another memory, in its
    own layout), nor are kernels (`custom-call`)."""
    comps = parse_computations(hlo_text)
    opcodes = {i["name"]: i["opcode"] for body in comps.values()
               for i in body}
    found: Dict[str, str] = {}
    for name, entry in _resolve(comps).items():
        parts = entry["scope"].split("/")
        if under not in parts or any(o in parts for o in outside):
            continue
        if entry["kind"] == "move" and \
                not opcodes[name].endswith(("-start", "-done")):
            found[name] = opcodes[name]
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
    for ins in filter(None, map(read_instruction, hlo_text.splitlines())):
        if ins["opcode"] != opcode:
            continue
        scope = scope_of(ins["op_name"])
        if not under or f"/{under}/" in f"/{scope}/":
            found[ins["name"]] = ins["shape"]
    return found
