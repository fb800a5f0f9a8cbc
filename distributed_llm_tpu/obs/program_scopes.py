"""Which ``jax.named_scope`` each operation of a compiled program belongs to.

A device trace names an event by its HLO instruction (``fusion.185``,
``paged_rows_decode.5``); the model code names its parts by scope
(``attention``, ``moe_experts``, ``kda_step``).  The two meet in one place
only: the metadata of the compiled (optimised) HLO, where every
instruction that came from traced code carries its ``op_name``, the stack
of scopes it was traced under.  ``op_scopes`` reads that text into

    {instruction: {"scope": <innermost named scope or None>,
                   "mixed": <a fusion whose work lies in several scopes>}}

for every instruction a trace can show: those of the entry computation
and of the computations its ``while``s, ``conditional``s and ``call``s
run (the layer scans are ``while`` bodies, which is where the time is),
not those inside a fused computation, which run as their fusion.  Names
are spelt as ``benchmark/tracing.py`` ``short_name`` leaves them, less a
custom call's ``@target``.  What the compiler itself put in (a ``copy``,
an async pair) carries no metadata and is named by what reads it.

A pure function of the text: ``engine/batching.py`` ``step_programs``
calls it on the engine's own tick and chunk programs when ``GET
/debug/programs`` asks, and never otherwise.

``pool_sized_moves`` reads the same text for the other thing only the
compiled program says: whether it leaves the pool where it rests, or
copies an array of it on the way in, round a loop, or on the way out.
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

# Instructions that only ENCLOSE others (``benchmark/tracing.py``
# ``WRAPPERS``): their time is their children's.
WRAPPERS = ("while", "conditional", "call")
# Instructions that run nothing: a trace never shows them.
_NO_DEVICE_TIME = {"parameter", "get-tuple-element", "tuple", "constant",
                   "bitcast", "after-all"}
# Inside a fusion: what moves or names data without computing (a hoisted
# constant keeps the scope it was traced under, which says nothing of the
# fusion that reads it), and what a fusion is made for: products, kernels,
# gathers and writes into a buffer.
_MOVES = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast",
          "bitcast-convert", "broadcast", "iota", "copy", "reshape",
          "transpose", "convert", "slice", "dynamic-slice", "concatenate",
          "pad", "fusion"}
_WORK = {"convolution", "dot", "custom-call", "gather", "scatter",
         "dynamic-update-slice", "sort", "reduce-window"}
# Components of an ``op_name`` that JAX's own machinery puts there.  A
# transform is spelt ``jit(f)``, ``vmap(f)``, ``transpose(jvp(f))``; an
# einsum leaves its subscripts, a closure its qualified name: neither is
# an identifier, so the identifier test drops them too.
_MACHINERY = {"while", "body", "cond", "closed_call", "checkpoint", "pjit",
              "remat", "core_call", "custom_jvp_call", "custom_vjp_call",
              "shard_map"}
_IDENTIFIER = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
_BRANCH = re.compile(r"^branch_\d+_fun$")

_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.-]+) = (.*)$")
_OPCODE = re.compile(r"^([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=%?([\w.-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.-]+)")


# A pool array of this many bytes or more is POOL-SIZED: a copy of it costs
# 0.03 ms or more at the 555 GB/s a transposing copy reaches on a v5e.
# Under it lie the vector of the rows' owners and the conv tails (8.3 MB
# at most in the benchmark's cells), whose pair of copies at a program's
# edge no trace of a cell shows (PERF.md section 7).
POOL_SIZED_BYTES = 16 << 20
# What may have a pool-shaped result in a program that updates the pool
# in place: the buffer coming in and going round (a ``while`` and a
# ``tuple`` are tuple-typed and never match) and the in-place writes.
_POOL_IN_PLACE = {"parameter", "get-tuple-element", "bitcast", "scatter",
                  "dynamic-update-slice"}
_HLO_TYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
              "int8": "s8", "int32": "s32", "uint32": "u32"}
# (ROOT, result type without its layout, opcode, the rest) of an instruction.
HLO_RESULT = re.compile(r"^\s*(ROOT )?%?[\w.-]+ = (\w+\[[\d,]*\])\S* "
                        r"([\w-]+)\((.*)$")


def pool_sized(x) -> bool:
    """Whether ``x`` (an array or its shape and dtype) is a pool-sized
    array: ``POOL_SIZED_BYTES`` or more."""
    return x.size * x.dtype.itemsize >= POOL_SIZED_BYTES


def row_major(major_to_minor) -> bool:
    """Whether a layout's ``major_to_minor`` is the one order a pool
    array rests in: its axes as written."""
    return tuple(major_to_minor) == tuple(range(len(major_to_minor)))


def pool_sized_moves(hlo_text: str, pool) -> Dict[str, int]:
    """A count by opcode of the instructions of a compiled pool program
    whose result is shaped like a pool-sized array of ``pool`` (arrays or
    shapes; ``POOL_SIZED_BYTES``) and that are neither the buffer going
    round nor an in-place write: a ``copy`` into another layout at the
    program's edge or a loop's, a stacked ``ys``, a slice fusion (a
    ``fusion`` is judged by its root).  ``{}`` says the program leaves
    the pool where it rests."""
    shapes = {f"{_HLO_TYPES[str(x.dtype)]}[{','.join(map(str, x.shape))}]"
              for x in pool.values() if pool_sized(x)}
    roots: Dict[str, str] = {}
    suspects, computation = [], None
    for line in hlo_text.splitlines():
        if line.endswith("{") and " = " not in line:
            words = line.split()
            computation = words[1 if words[0] == "ENTRY" else 0].lstrip("%")
            continue
        m = HLO_RESULT.match(line)
        if not m or m[2] not in shapes:
            continue
        if m[1]:
            roots[computation] = m[3]
        if m[3] not in _POOL_IN_PLACE:
            suspects.append((m[3], m[4]))
    moves: Dict[str, int] = {}
    for op, rest in suspects:
        called = re.search(r"calls=%?([\w.-]+)", rest)
        if (op == "fusion" and called
                and roots.get(called[1]) in _POOL_IN_PLACE):
            continue
        moves[op] = moves.get(op, 0) + 1
    return moves


class _Instruction(NamedTuple):
    name: str
    opcode: Optional[str]
    op_name: Optional[str]
    root: bool
    called: Dict[str, object]      # computations it names, by attribute
    reads: List[str]               # the instructions it reads


def scope_stack(op_name: Optional[str]) -> Tuple[str, ...]:
    """The ``jax.named_scope``s of an ``op_name``, outermost first
    (``jit(decode_tick)/while/body/closed_call/mixer_proj/attention/
    dot_general`` gives ``("mixer_proj", "attention")``): the components
    before the primitive's own that are identifiers and none of JAX's
    wrappers."""
    if not op_name:
        return ()
    parts = op_name.split("/")
    # A Pallas kernel's own ``name=`` stands before its ``pallas_call``.
    parts = parts[:-2] if parts[-1] == "pallas_call" else parts[:-1]
    return tuple(part for part in parts
                 if _IDENTIFIER.match(part) and part not in _MACHINERY
                 and not _BRANCH.match(part))


def innermost_scope(op_name: Optional[str]) -> Optional[str]:
    """The innermost named scope of an ``op_name``, or None."""
    stack = scope_stack(op_name)
    return stack[-1] if stack else None


def _opcode(rest: str) -> Optional[str]:
    """The opcode of an instruction's right-hand side: what follows its
    result type, which is one word or a parenthesised tuple."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = _OPCODE.match(rest.lstrip())
    return m[1] if m else None


def _computations(hlo_text: str):
    """{computation: [instruction, ...]} and the entry's name."""
    comps: Dict[str, List[_Instruction]] = {}
    entry, current = None, None
    for line in hlo_text.splitlines():
        if line.endswith("{") and " = " not in line:
            words = line.split()
            is_entry = words[0] == "ENTRY"
            current = words[1 if is_entry else 0].lstrip("%")
            comps[current] = []
            if is_entry:
                entry = current
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if not m:
            continue
        rest = m[3]
        named = _OP_NAME.search(rest)
        called = dict(_CALLED.findall(rest))
        branches = _BRANCHES.search(rest)
        if branches:
            called["branches"] = [b.strip().lstrip("%")
                                  for b in branches[1].split(",")]
        # Operands stand between the opcode's parentheses, which no
        # attribute precedes.
        reads = _OPERAND.findall(rest.split("), ", 1)[0])
        comps[current].append(_Instruction(
            m[2], _opcode(rest), named and named[1], bool(m[1]), called,
            reads))
    return comps, entry


def op_scopes(hlo_text: str) -> Dict[str, Dict[str, object]]:
    """``{instruction: {"scope", "mixed"}}`` of a compiled program's text
    (``compiled.as_text()``), as the module's docstring says.  A fusion
    is named by ``_fusion_scope``: a product under ``mixer_proj`` fused
    with the sums of the next sublayer's norm under ``ffn`` is the
    product's, a sum fused with a sum is its root's, and one that writes
    under ``kv_write`` and multiplies under ``attention`` is ``mixed``:
    it belongs to neither."""
    comps, entry = _computations(hlo_text)
    if entry is None:
        return {}
    shown: Dict[str, None] = {}            # in the order found
    todo = [entry]
    while todo:
        comp = todo.pop()
        if comp in shown or comp not in comps:
            continue
        shown[comp] = None
        for ins in comps[comp]:
            if ins.opcode in WRAPPERS:
                todo += [c for k, c in ins.called.items() if k != "branches"]
                todo += ins.called.get("branches", [])
    out: Dict[str, Dict[str, object]] = {}
    for comp in shown:
        for ins in comps[comp]:
            if ins.opcode in WRAPPERS or ins.opcode in _NO_DEVICE_TIME:
                continue
            scope, mixed = innermost_scope(ins.op_name), False
            if ins.opcode == "fusion" and ins.called.get("calls") in comps:
                scope, mixed = _fusion_scope(comps[ins.called["calls"]],
                                             scope)
            out[ins.name] = {"scope": scope, "mixed": mixed}
        _name_the_compilers_own(comps[comp], out)
    return out


def _fusion_scope(parts, own: Optional[str]):
    """(scope, mixed) of a fusion from the instructions of its fused
    computation.  Its products, kernels, gathers and writes decide, where
    it holds any: the fusion is theirs, and a norm's sums or an epilogue
    fused in across a sublayer's edge are glue.  Where it holds none its
    arithmetic decides, and the fusion is its root's (else its own
    metadata's ``own``, else the deepest scope named).  ``mixed``: the
    deciding parts name scopes that do not nest in one another."""
    named = [p for p in parts if scope_stack(p.op_name)]
    work = [p for p in named if p.opcode in _WORK]
    deciding = (work or [p for p in named if p.opcode not in _MOVES]
                or named)
    stacks = {scope_stack(p.op_name) for p in deciding}
    deepest = max(stacks, key=len, default=())
    mixed = any(s != deepest[:len(s)] for s in stacks)
    if work and not mixed:
        return deepest[-1], False
    root = next((innermost_scope(p.op_name) for p in parts if p.root), None)
    return root or own or (deepest[-1] if deepest else None), mixed


def _name_the_compilers_own(instructions, out) -> None:
    """An instruction the compiler put in (a ``copy``, an async pair, a
    buffer) has no metadata; it exists for what reads its result, so it
    takes that reader's scope (a ``while``'s own, where a loop reads it),
    through further instructions without metadata, else the scope of what
    made its operand."""
    readers: Dict[str, List[str]] = {}
    reads_of, named, loops = {}, {}, set()
    for ins in instructions:
        reads_of[ins.name] = ins.reads
        named[ins.name] = ins.op_name
        if ins.opcode in WRAPPERS:
            loops.add(ins.name)
        for operand in ins.reads:
            readers.setdefault(operand, []).append(ins.name)

    def seek(name, links, depth=0):
        for other in links.get(name, ()):
            if other in out:
                found = out[other]["scope"]
                blind = not named[other] and not out[other]["mixed"]
            else:
                found = innermost_scope(named.get(other))
                blind = not named.get(other) and other not in loops
            if found is None and blind and depth < 4:
                found = seek(other, links, depth + 1)
            if found is not None:
                return found
        return None

    for ins in instructions:
        if (ins.name in out and out[ins.name]["scope"] is None
                and not ins.op_name):
            out[ins.name]["scope"] = (seek(ins.name, readers)
                                      or seek(ins.name, reads_of))
