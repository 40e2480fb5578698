"""Read a compiled program's text for cache-sized work inside its loops.

A decode step has to read the weights, read the view of K/V it attends and
write one token a layer. What it must not do is move the KV pool or a whole
layer of it from one buffer to another inside a loop (the decode chunk's
loop over steps, forward's loop over layers): that is bytes moved, not
work, and it scales with the pool (PERF.md §6, PR 27). Whether it does is a
property of the compiled program, so it is read there:
``jax.jit(f).lower(...).compile().as_text()``.

``pool_sized_loop_ops(text, cache)`` returns the instructions inside any
``while`` body (nested bodies and called computations included) whose
result has the shape of a whole cache leaf, or of one layer of ``k`` / ``v``
/ their scales, and that are not an in-place update of a small part:

- allowed: ``dynamic-update-slice`` and ``scatter`` (alone, or as the root
  of a fusion) whose operand is the loop's own buffer and whose update is
  smaller than a layer of the leaf written (for ``state`` / ``conv``: one
  layer, which changes whole every step);
- allowed: what moves no bytes (``parameter``, ``tuple``,
  ``get-tuple-element``, ``bitcast``, ``while``, ``call``,
  ``conditional``, ``optimization-barrier``, the TPU compiler's
  ``AllocateBuffer``);
- everything else of such a shape is reported: ``copy``, ``dynamic-slice``
  of a whole layer, a ``dynamic-update-slice`` that writes a whole layer
  back, a ``select`` or ``convert`` over the pool.

``entry_param_copies(text)`` reads the other place bytes are moved without
work: the entry computation, once a call. A program compiled for a
parameter in one layout whose loops want it in another copies the whole
parameter there before anything else runs (PERF.md §6, PR 33: 3.7 GiB of
``falcon-7b``'s weights every decode chunk). Each such copy names the
parameter and the layout the program wants it in, which is what
``serve/weight_layout.py`` places the weights by;
``param_sized_entry_copies(text, shapes)`` is the same list as lines, for
the leaves of a parameter tree.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (.*)$")
_SHAPE = re.compile(r"^([a-z]\w*)\[([\d,]*)\]")
_OPCODE = re.compile(r"^([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")

# numpy's dtype names as the compiled text spells them.
_DTYPE_NAMES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
                "int8": "s8", "int32": "s32", "uint8": "u8"}
_FREE = frozenset({
    "parameter", "tuple", "get-tuple-element", "bitcast", "while", "call",
    "conditional", "optimization-barrier", "constant", "after-all"})
_IN_PLACE = frozenset({"dynamic-update-slice", "scatter"})


@dataclasses.dataclass
class Instr:
    name: str
    dtype: Optional[str]          # None for a tuple result
    dims: Tuple[int, ...]
    opcode: str
    operands: List[str]
    called: List[str]
    root: bool
    line: str

    @property
    def size(self) -> int:
        return math.prod(self.dims)


def _closing(text: str, depth: int = 0) -> int:
    """Index of the parenthesis that brings ``depth`` back to zero."""
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i
    return len(text)


def _split_shape(rest: str) -> Tuple[Optional[str], Tuple[int, ...], str]:
    """(dtype, dims, what follows the result shape) of an instruction's
    right-hand side; a tuple shape gives (None, (), …)."""
    if rest.startswith("("):
        return None, (), rest[_closing(rest) + 1:].lstrip()
    m = _SHAPE.match(rest)
    if not m:
        return None, (), rest
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    after = rest[m.end():]
    if after.startswith("{"):          # layout, tiling, memory space
        after = after[after.index("}") + 1:]
    return m.group(1), dims, after.lstrip()


def parse(text: str) -> Dict[str, List[Instr]]:
    """Computation name -> its instructions, from ``as_text()``."""
    comps: Dict[str, List[Instr]] = {}
    cur: Optional[List[Instr]] = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line) if cur is not None else None
        if not m:
            continue
        dtype, dims, after = _split_shape(m.group(3))
        op = _OPCODE.match(after)
        if not op:
            continue
        args = after[op.end():]
        end = _closing(args, 1)
        attrs = args[end:]
        called = _CALLED.findall(attrs)
        for group in _BRANCHES.findall(attrs):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        cur.append(Instr(m.group(2), dtype, dims, op.group(1),
                         _OPERAND.findall(args[:end]), called,
                         bool(m.group(1)), line.strip()))
    return comps


def _loop_computations(comps: Dict[str, List[Instr]]) -> List[str]:
    """Every computation that runs inside some while body, outermost
    first. Fused computations are not walked: a fusion is judged as one
    instruction of its caller."""
    seen: List[str] = []
    todo = [c for instrs in comps.values() for i in instrs
            if i.opcode == "while" for c in i.called]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.append(name)
        for i in comps[name]:
            if i.opcode in ("while", "call", "conditional"):
                todo += i.called
    return seen


def _update_size(instr: Instr, comp: List[Instr],
                 comps: Dict[str, List[Instr]]) -> Optional[int]:
    """Elements written by an in-place update (alone or the root of a
    fusion, through bitcasts), or None if the instruction is no such
    update of a buffer that was handed in."""
    by_name = {i.name: i for i in comp}
    if instr.opcode == "fusion":
        comp = comps.get(instr.called[0], []) if instr.called else []
        by_name = {i.name: i for i in comp}
        instr = next((i for i in comp if i.root), None)
        while instr is not None and instr.opcode == "bitcast":
            instr = by_name.get(instr.operands[0])
    if instr is None or instr.opcode not in _IN_PLACE:
        return None
    target = by_name.get(instr.operands[0])
    while target is not None and target.opcode == "bitcast":
        target = by_name.get(target.operands[0])
    if target is None or target.opcode not in ("parameter",
                                               "get-tuple-element"):
        return None                     # writes into a fresh value
    update = by_name.get(
        instr.operands[1 if instr.opcode == "dynamic-update-slice" else 2])
    return None if update is None else update.size


def cache_shapes(cache) -> Dict[Tuple[str, Tuple[int, ...]], int]:
    """(dtype, dims) -> the largest in-place update allowed there, for a
    KVCache (or its ShapeDtypeStructs), a leaf by what its declaration
    says of it (models/transformer.LEAF_TRAITS). A leaf with a token axis
    takes token-sized writes, anything under a layer of it: with a slot
    axis that holds of the leaf whole and of one layer of it, sliced out
    either way; of a window layer's ring only of the whole leaf, one layer
    of a ring being what a decode step reads (a window and a margin long, by
    design). The leaves with no token axis, the recurrent ones, change a
    whole layer at a time."""
    from runbooks_tpu.models.transformer import LEAF_TRAITS

    shapes: Dict[Tuple[str, Tuple[int, ...]], int] = {}
    for field, traits in LEAF_TRAITS.items():
        leaf = getattr(cache, field)
        if leaf is None or 0 in leaf.shape:   # a latent cache's empty k, v
            continue
        dtype = _DTYPE_NAMES[str(leaf.dtype)]
        dims = tuple(int(d) for d in leaf.shape)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:       # the text has one device's shapes
            dims = tuple(sharding.shard_shape(dims))
        layer = math.prod(dims[1:])
        # (A row's compressed keys are rewritten a layer at a time by a
        # prefill, an entry at a time by a decode step.)
        whole_layer = traits.tokens in (None, "compressed")
        shapes[(dtype, dims)] = layer if whole_layer else layer - 1
        if traits.tokens == "slots":
            shapes[(dtype, dims[1:])] = layer - 1
            shapes[(dtype, (1,) + dims[1:])] = layer - 1
    return shapes


def pool_sized_loop_ops(text: str, cache) -> List[str]:
    """The offending instructions, as ``<computation>: <opcode> <shape>
    <name>`` lines; empty for a program that updates its cache in place."""
    comps = parse(text)
    shapes = cache_shapes(cache)
    found = []
    for name in _loop_computations(comps):
        for i in comps[name]:
            limit = shapes.get((i.dtype, i.dims))
            if limit is None or i.opcode in _FREE:
                continue
            if 'custom_call_target="AllocateBuffer"' in i.line:
                continue                # reserves, moves nothing
            written = _update_size(i, comps[name], comps)
            if written is not None and written <= limit:
                continue
            dims = ",".join(map(str, i.dims))
            found.append(f"{name}: {i.opcode} {i.dtype}[{dims}] {i.name}")
    return found


_ENTRY = re.compile(r"^ENTRY %?([\w.\-]+) ", re.M)
_RESULT_LAYOUT = re.compile(r"= [a-z]\w*\[[\d,]*\](\{[^}]*\})")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_PARAM_NO = re.compile(r"parameter\((\d+)\)")
# What hands a value on as it is: a prefetch into another memory space is
# the pair copy-start / copy-done, and the re-layout, if any, follows it.
_PASSES_ON = frozenset({"bitcast", "copy-done", "copy-start"})


@dataclasses.dataclass
class EntryCopy:
    """One whole-parameter copy in an entry computation."""
    param: str                    # the parameter's op name: "params['embed']"
    number: int                   # parameter(N)
    dtype: str
    dims: Tuple[int, ...]
    layout: str                   # the copy's result: "{1,0:T(8,128)(2,1)}"
    opcode: str
    name: str


def entry_param_copies(text: str) -> List[EntryCopy]:
    """The ``copy`` and ``transpose`` instructions of the entry computation
    that take a whole entry parameter (as it is, or through a prefetch)
    into another layout of the device's main memory. A copy whose result
    lives in another memory space (``S(n)`` in its layout) is a prefetch
    the scheduler placed: the parameter's one read, not a second."""
    m = _ENTRY.search(text)
    entry = parse(text).get(m.group(1), []) if m else []
    by_name = {i.name: i for i in entry}
    found = []
    for i in entry:
        if i.opcode not in ("copy", "transpose") or not i.operands:
            continue
        layout = _RESULT_LAYOUT.search(i.line)
        if layout is None or "S(" in layout.group(1):
            continue
        src = by_name.get(i.operands[0])
        while src is not None and src.opcode in _PASSES_ON and src.operands:
            src = by_name.get(src.operands[0])
        if src is None or src.opcode != "parameter" or src.size != i.size:
            continue
        op_name = _OP_NAME.search(src.line)
        found.append(EntryCopy(
            param=op_name.group(1).replace("\\'", "'") if op_name else "",
            number=int(_PARAM_NO.search(src.line).group(1)),
            dtype=i.dtype, dims=i.dims, layout=layout.group(1),
            opcode=i.opcode, name=i.name))
    return found


def param_sized_entry_copies(text: str, shapes) -> List[str]:
    """The whole-parameter copies of the entry computation whose source has
    the shape of a leaf of ``shapes`` (a parameter tree, or its
    ShapeDtypeStructs; under a sharding, one device's part), as ``<opcode>
    <shape> <name> <- <parameter>`` lines; empty for a program that reads
    its parameters where they lie."""
    import jax

    leaves = set()
    for leaf in jax.tree.leaves(shapes):
        dims = tuple(int(d) for d in leaf.shape)
        sharding = getattr(leaf, "sharding", None)
        if hasattr(sharding, "shard_shape"):
            dims = tuple(sharding.shard_shape(dims))
        leaves.add((_DTYPE_NAMES.get(str(leaf.dtype), str(leaf.dtype)),
                    tuple(sorted(dims))))
    return [f"{c.opcode} {c.dtype}[{','.join(map(str, c.dims))}] {c.name} "
            f"<- {c.param}"
            for c in entry_param_copies(text)
            if (c.dtype, tuple(sorted(c.dims))) in leaves]
