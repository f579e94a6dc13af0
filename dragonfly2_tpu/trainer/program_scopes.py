"""A compiled program's text with every operation named by the scope of
the source it came from: what ``OnlineGraphTrainer.dispatch_program_text``
hands to the readers that join a device trace to the step's
``jax.named_scope``s (``benchmark/reduce/stream_scopes.py``,
``benchmark/tools/program_trace.py``, ``stream_trace.py``).

XLA carries a source instruction's ``op_name`` through its passes, with
two kinds of exception that this module repairs in the text, and nowhere
else:

- **the grouped products**: the TPU compiler rewrites every
  ``jax.lax.ragged_dot`` into a Mosaic custom call whose line says
  ``metadata={op_name="ragged-dot-none"}`` and calls no computation, so
  the scope is gone from the compiled text (PERF.md section 3).  The
  unoptimised module of the same ``lower()`` still has it on each
  ``chlo.ragged_dot``; ``source_products`` reads it there, and
  ``restore`` matches the compiled products to it by their types and the
  loop each sits in, every product exactly once.  The group table each
  product reads (``ragged-dot-metadata``), made by the same rewrite,
  takes the op_name of the products it feeds.
- **what XLA makes with no source instruction** (layout copies, a loop
  carry's copies, asynchronous copies and slices, what carries no
  ``op_name`` or only XLA's own name for it): the op_name of the
  instruction it copies for, its operand's, else its user's, looked for
  through tuples and their parts, and from a loop's condition or body to
  the loop.

Nothing but ``op_name``s changes: a line that had no metadata gains
``metadata={op_name="..."}`` in the form XLA writes it.
"""

from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r"metadata=\{([^}]*)\}")
_CALLED = re.compile(
    r"\b(body|condition|to_apply|branch_computations|true_computation|false_computation)="
    r"(\{[^}]*\}|%[\w.\-]+)"
)
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_TENSOR = re.compile(r"tensor<((?:\d+x)*)([a-z]+\d*)>")
# Instructions that compute nothing of their own: they pass a value on.
INERT = frozenset({"parameter", "constant", "tuple", "get-tuple-element", "bitcast"})
# What makes a computation's instructions operations of their own on the
# device (a fusion's or a reducer's are not: the trace shows the caller).
_CONTROL = frozenset({"while", "conditional", "call"})
# jax's primitives that stage a function (a call, a loop, a rematerialised
# block): an operation of another kind named by one was made by XLA.
_STAGED = frozenset({"closed_call", "while", "remat2", "checkpoint", "pjit", "custom_vjp_call", "custom_jvp_call"})
_PRODUCT_STEM = "ragged-dot"
_PRODUCT_TABLE = "ragged-dot-metadata"


class SourceProduct(NamedTuple):
    """A ``ragged_dot`` of the unoptimised module: its ``op_name``, its
    result's and operands' types as the compiled text writes them
    (``f32[32768,768]``), and the ``op_name`` of the loop it sits in
    ("" outside every loop)."""

    op_name: str
    types: Tuple[str, ...]
    loop: str


@dataclass(eq=False)
class _Instruction:
    index: int                   # its line
    name: str
    computation: str
    opcode: str
    operands: List[str]
    op_name: Optional[str]
    called: List[str]            # the computations a loop, branch or call runs
    root: bool


def _split(line: str, at: int):
    """(opcode, operand names, the rest) of an instruction's line from the
    character after ``= ``."""
    if line.startswith("(", at):              # a tuple's type holds spaces
        depth = 0
        for end in range(at, len(line)):
            depth += (line[end] == "(") - (line[end] == ")")
            if depth == 0:
                break
        kind_end = end + 1
    else:
        kind_end = line.index(" ", at)
    opcode_at = kind_end + 1
    opened = line.index("(", opcode_at)
    closed = line.index(")", opened)
    operands = re.findall(r"%([\w.\-]+)", line[opened:closed])
    return line[opcode_at:opened], operands, line[closed:]


def _parse(lines: List[str]):
    """({name: instruction}, the same in the text's order) of the
    instructions that run as operations of their own: the entry
    computation's and those of the loops, branches and calls it reaches.
    A fusion's or a reducer's computation (the trace shows its caller) is
    not read at all, which is most of the text."""
    heads, entry = {}, None
    for index, line in enumerate(lines):
        if line[:1] in ("%", "E") and line.rstrip().endswith("{"):
            found = _COMPUTATION.match(line)
            if found:
                heads[found.group(1)] = index
                if line.startswith("ENTRY"):
                    entry = found.group(1)
    instructions: Dict[str, _Instruction] = {}
    todo, seen = ([entry] if entry else []), set()
    while todo:
        computation = todo.pop()
        if computation in seen or computation not in heads:
            continue
        seen.add(computation)
        for index in range(heads[computation] + 1, len(lines)):
            line = lines[index]
            head = _INSTRUCTION.match(line)
            if head is None:
                if line.startswith("}"):
                    break
                continue
            opcode, operands, rest = _split(line, head.end())
            found = _OP_NAME.search(rest)
            called = [
                name for _, group in _CALLED.findall(rest) for name in re.findall(r"%([\w.\-]+)", group)
            ] if opcode in _CONTROL else []
            ins = _Instruction(
                index, head.group(2), computation, opcode, operands,
                found.group(1) if found else None, called, bool(head.group(1)),
            )
            instructions[ins.name] = ins
            todo.extend(called)
    return instructions, sorted(instructions.values(), key=lambda i: i.index)


def operations(text: str) -> List[Tuple[str, str, Optional[str]]]:
    """(name, opcode, op_name) of every instruction of a compiled module's
    text that runs as an operation of its own, in the text's order."""
    return [(i.name, i.opcode, i.op_name) for i in _parse(text.split("\n"))[1]]


def _sourced(ins: _Instruction) -> bool:
    """Whether ``ins`` has the ``op_name`` jax wrote for it from the source:
    a path, not a name XLA made up (``ragged-dot-none``, a parameter's)
    or none, and not the name of a call or loop that XLA's inlining hands
    to what it makes inside one (a copy named ``.../closed_call``)."""
    if not ins.op_name or "/" not in ins.op_name:
        return False
    return ins.opcode in _CONTROL or ins.op_name.rpartition("/")[2] not in _STAGED


def _shape(text: str) -> str:
    found = _SHAPE.match(text)
    return f"{found.group(1)}[{found.group(2)}]" if found else text


def _tensor(mlir_type: str) -> str:
    found = _TENSOR.fullmatch(mlir_type)
    if not found:
        return mlir_type
    return f"{found.group(2)}[{','.join(d for d in found.group(1).split('x') if d)}]"


def _loc_name(location) -> str:
    """The name jax gave an MLIR operation's location: its ``op_name``."""
    found = re.match(r'loc\("([^"]*)"', str(location))
    return found.group(1) if found else ""


def source_products(lowered) -> List[SourceProduct]:
    """Every ``ragged_dot`` of ``lowered``'s unoptimised module (a
    ``jax.stages.Lowered``), read off its operations: no text is printed,
    so it costs milliseconds where printing the module costs seconds."""
    from jaxlib.mlir import ir

    out: List[SourceProduct] = []

    def visit(op):
        # A product whose location names nothing is left out: the count
        # then fails to match, and its type keeps the compiler's name.
        if op.name.endswith("ragged_dot") and _loc_name(op.location):
            loop, up = "", op.parent
            while up is not None and up.name != "builtin.module":
                if up.name == "stablehlo.while":
                    loop = _loc_name(up.location)
                    break
                up = up.parent
            types = (str(op.results[0].type), *(str(o.type) for o in op.operands[:2]))
            out.append(SourceProduct(_loc_name(op.location), tuple(_tensor(t) for t in types), loop))
        return ir.WalkResult.ADVANCE

    lowered.compiler_ir("stablehlo").operation.walk(visit)
    return out


def _product_types(line: str) -> Tuple[str, ...]:
    """(result, left factor, right factor) of a compiled product: the two
    factors are the custom call's last two operands, whose types its
    ``operand_layout_constraints`` list."""
    at = line.index(" = ") + 3
    result = _shape(line[at:])
    found = re.search(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=", line)
    shapes = [f"{t}[{d}]" for t, d in _SHAPE.findall(found.group(1))] if found else []
    return (result, *shapes[-2:])


def _loop_of(ins: _Instruction, callers) -> str:
    """The ``op_name`` of the nearest loop around ``ins`` ("" if none)."""
    computation = ins.computation
    while computation in callers:
        caller = callers[computation]
        if caller.opcode == "while":
            return caller.op_name or ""
        computation = caller.computation
    return ""


def _match_products(products, sources, lines, callers) -> Dict[str, str]:
    """{compiled product: op_name}: each compiled product takes the
    ``op_name`` of the source products of its types whose loop is the
    one it sits in, when they agree on one; kept only where every product
    of those types finds its source and each source is used once."""
    out: Dict[str, str] = {}
    by_types: Dict[Tuple[str, ...], List[_Instruction]] = {}
    for ins in products:
        by_types.setdefault(_product_types(lines[ins.index]), []).append(ins)
    for types, compiled in by_types.items():
        mine = [s for s in sources if s.types == types]
        if len(mine) != len(compiled):
            continue
        got, used = {}, Counter()
        for ins in compiled:
            loop = _loop_of(ins, callers)
            fits = [s for s in mine if s.loop and (loop == s.loop or loop.endswith("/" + s.loop))]
            if not fits:
                fits = [s for s in mine if not s.loop and not loop]
            longest = max((len(s.loop) for s in fits), default=-1)
            names = {s.op_name for s in fits if len(s.loop) == longest}
            if len(names) != 1:
                break
            (name,) = names
            prefix = loop[: len(loop) - longest] if longest > 0 else ""
            got[ins.name] = prefix + name
            used[name] += 1
        else:
            if used == Counter(s.op_name for s in mine):
                out.update(got)
    return out


def _write(line: str, op_name: str) -> str:
    """``line`` with ``op_name`` as its ``op_name``, in XLA's form."""
    meta = _METADATA.search(line)
    if meta is None:
        field = f'metadata={{op_name="{op_name}"}}'
        at = line.find(", backend_config=")
        return f"{line}, {field}" if at < 0 else f"{line[:at]}, {field}{line[at:]}"
    inner = meta.group(1)
    if _OP_NAME.search(inner):
        inner = _OP_NAME.sub(lambda _: f'op_name="{op_name}"', inner, count=1)
    else:
        inner = f'op_name="{op_name}"' + (f" {inner}" if inner else "")
    return f"{line[:meta.start()]}metadata={{{inner}}}{line[meta.end():]}"


def restore(text: str, sources: Iterable[SourceProduct] = ()) -> str:
    """``text`` (a compiled module's) with the grouped products' and the
    XLA-made operations' ``op_name`` restored, as the module docstring
    says; every other character as it was."""
    lines = text.split("\n")
    instructions, operations = _parse(lines)
    callers = {c: ins for ins in instructions.values() for c in ins.called}
    users: Dict[str, List[_Instruction]] = {}
    for ins in instructions.values():
        for name in ins.operands:
            users.setdefault(name, []).append(ins)
    products = {
        i.name: i for i in operations
        if i.opcode == "custom-call" and i.name.startswith(_PRODUCT_STEM) and not i.name.startswith(_PRODUCT_TABLE)
    }
    named = _match_products(list(products.values()), list(sources), lines, callers)
    for ins in operations:                    # the products' group tables
        if ins.name.startswith(_PRODUCT_TABLE):
            parts = [u for u in users.get(ins.name, ()) if u.opcode == "get-tuple-element"]
            fed = {named.get(u.name) for g in parts for u in users.get(g.name, ()) if u.name in products}
            if len(fed) == 1 and None not in fed:
                named[ins.name] = fed.pop()

    def own(ins: _Instruction) -> Optional[str]:
        """The op_name ``ins`` hands a neighbour: the one restored here,
        else the one jax wrote for it."""
        if ins.name in named:
            return named[ins.name]
        return ins.op_name if ins.opcode not in INERT and _sourced(ins) else None

    def from_operands(ins: _Instruction, depth: int = 0) -> Optional[str]:
        """The first op_name among ``ins``'s operands, looked for through
        tuples and their parts; not through a loop's parameter, its carry,
        which no one instruction made."""
        for name in ins.operands:
            op = instructions.get(name)
            if op is None:
                continue
            passes = op.opcode in INERT and op.opcode != "parameter" and depth < 8
            got = own(op) or (from_operands(op, depth + 1) if passes else None)
            if got:
                return got
        return None

    def from_users(ins: _Instruction, depth: int = 0) -> Optional[str]:
        """The first op_name among what reads ``ins``, looked for through
        tuples and their parts, and from a computation's result (a loop's
        condition, its body's tuple) to the loop or call that runs it."""
        readers = list(users.get(ins.name, ()))
        if ins.root and ins.computation in callers:
            readers.append(callers[ins.computation])
        for user in readers:
            passes = (user.opcode in INERT or user.opcode in _CONTROL) and depth < 8
            got = own(user) or (from_users(user, depth + 1) if passes else None)
            if got:
                return got
        return None

    made = [
        i for i in operations
        if i.opcode not in INERT and i.name not in named and i.name not in products and not _sourced(i)
    ]
    for ins in made:                          # the operand's, in the order they run
        got = from_operands(ins)
        if got:
            named[ins.name] = got
    for ins in reversed(made):                # else the user's, last first
        if ins.name not in named:
            got = from_users(ins)
            if got:
                named[ins.name] = got
    for name, op_name in named.items():
        at = instructions[name].index
        lines[at] = _write(lines[at], op_name)
    return "\n".join(lines)
