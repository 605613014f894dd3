"""Lift optimized HLO text into a structured collective graph.

``compiled.as_text()`` (post-SPMD-partitioning HLO) is the only place
the GSPMD-inserted collectives are visible.  This module parses that
text into per-op records — opcode, operand/result dtypes and byte
sizes, replica groups, channel ids, source-target pairs — plus a full
instruction symbol table for dataflow queries (who consumes a
collective's result).  It supersedes the aggregate-only
``CollectiveStats`` in :mod:`repro.utils.hlo`, which now delegates
here.

Parser notes (each pinned by the corpus under ``tests/data/hlo/``):

- dtype widths are in **bits** so the packed 4-bit types (``s4``/``u4``)
  size correctly (a byte table silently counted them as 0);
- modern HLO prints operand types inline
  (``all-gather(s8[1,96]{1,0} %fusion)``) — those are preferred, with a
  two-pass symbol-table fallback for operands spelled as bare ``%refs``;
- async ``-start``/``-done`` pairs count ONCE (at the ``-start``), and a
  start op's tuple result drops the leading operand-alias elements so
  result bytes reflect the gathered output, not operand+output;
- tuple result types are scanned with a balanced-paren walk, so layouts
  containing parens (``{0:T(256)}``) cannot truncate the tuple.

This module is intentionally pure (re + dataclasses only): it is
imported by ``repro.utils.hlo`` at package-import time and must not
drag in jax or the rest of ``repro``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import reduce

# Bit widths per HLO dtype. 4-bit types are genuine sub-byte dtypes:
# byte counts round up per *shape*, not per element (s4[96] = 48 bytes).
DTYPE_BITS = {
    "pred": 8, "s2": 2, "u2": 2, "s4": 4, "u4": 4,
    "s8": 8, "u8": 8,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3": 8, "f8e3m4": 8,
    "f8e4m3b11fnuz": 8, "f8e5m2fnuz": 8, "f8e4m3fnuz": 8, "f8e8m0fnu": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64, "c128": 128,
}

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_LINE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z][a-z0-9]*)\[(?P<dims>[\d,]*)\]")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")
_GROUPS_RE = re.compile(r"replica_groups=\{((?:\{[\d, ]*\},?)*)\}")
_GROUP_RE = re.compile(r"\{([\d, ]*)\}")
_IOTA_RE = re.compile(
    r"replica_groups=\[(?P<lhs>[\d,]+)\]<=\[(?P<dims>[\d,]+)\]"
    r"(?:T\((?P<perm>[\d,]+)\))?")


@dataclass(frozen=True)
class Shape:
    """One array shape: dtype, dims, and its padded byte size."""
    dtype: str
    dims: tuple[int, ...]

    @property
    def elems(self) -> int:
        return reduce(lambda a, b: a * b, self.dims, 1)

    @property
    def bytes(self) -> int:
        return (self.elems * DTYPE_BITS[self.dtype] + 7) // 8


def parse_shapes(type_str: str) -> tuple[Shape, ...]:
    """All array shapes in an HLO type string (tuples flattened)."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group("dt")
        if dt not in DTYPE_BITS:
            continue
        dims = m.group("dims")
        out.append(Shape(dt, tuple(int(d) for d in dims.split(","))
                         if dims else ()))
    return tuple(out)


def _shapes_bytes(shapes: tuple[Shape, ...]) -> int:
    return sum(s.bytes for s in shapes)


@dataclass(frozen=True)
class Instruction:
    """Symbol-table entry: every parsed HLO instruction."""
    name: str
    op: str
    result_shapes: tuple[Shape, ...]
    operand_names: tuple[str, ...]

    @property
    def result_bytes(self) -> int:
        return _shapes_bytes(self.result_shapes)


@dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction with its wire-relevant attributes."""
    kind: str                      # base opcode, e.g. "all-gather"
    name: str
    operand_names: tuple[str, ...]
    operand_shapes: tuple[Shape, ...]
    result_shapes: tuple[Shape, ...]
    replica_groups: tuple[tuple[int, ...], ...] | None
    channel_id: int | None
    source_target_pairs: tuple[tuple[int, int], ...] | None
    asynchronous: bool = False     # was a -start op

    @property
    def operand_bytes(self) -> int:
        return _shapes_bytes(self.operand_shapes)

    @property
    def result_bytes(self) -> int:
        return _shapes_bytes(self.result_shapes)

    @property
    def operand_dtypes(self) -> tuple[str, ...]:
        return tuple(s.dtype for s in self.operand_shapes)

    @property
    def result_dtypes(self) -> tuple[str, ...]:
        return tuple(s.dtype for s in self.result_shapes)

    def signature(self) -> tuple:
        """Structural identity ignoring instruction names/channel ids —
        what the membership-invariant rule compares across compiles.
        Plain nested tuples so signatures sort/compare reliably."""
        return (self.kind,
                tuple((s.dtype, s.dims) for s in self.operand_shapes),
                tuple((s.dtype, s.dims) for s in self.result_shapes),
                self.replica_groups or (),
                self.source_target_pairs or ())


def _scan_balanced(s: str, start: int) -> tuple[str, int]:
    """Content between s[start]=='(' and its match; returns (inner, end)
    with end just past the closing paren."""
    assert s[start] == "("
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return s[start + 1:i], i + 1
    return s[start + 1:], len(s)


def _split_top(s: str) -> list[str]:
    """Split on top-level commas (commas inside (), {}, [] don't count)."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def _iota_replica_groups(lhs: tuple[int, ...], dims: tuple[int, ...],
                         perm: tuple[int, ...] | None
                         ) -> tuple[tuple[int, ...], ...]:
    """Expand the iota replica-group form ``[g,s]<=[dims](T(perm))``."""
    n = reduce(lambda a, b: a * b, dims, 1)
    ids = list(range(n))
    if perm:
        # reshape iota(n) to dims, transpose by perm, flatten
        strides = [0] * len(dims)
        acc = 1
        for i in range(len(dims) - 1, -1, -1):
            strides[i] = acc
            acc *= dims[i]
        pdims = [dims[p] for p in perm]
        pstrides = [strides[p] for p in perm]
        out = []
        idx = [0] * len(pdims)
        for _ in range(n):
            out.append(sum(i * s for i, s in zip(idx, pstrides)))
            for ax in range(len(pdims) - 1, -1, -1):
                idx[ax] += 1
                if idx[ax] < pdims[ax]:
                    break
                idx[ax] = 0
        ids = out
    groups, size = lhs[0], reduce(lambda a, b: a * b, lhs[1:], 1)
    return tuple(tuple(ids[g * size:(g + 1) * size])
                 for g in range(groups))


def _parse_attrs(attrs: str):
    channel = None
    m = _CHANNEL_RE.search(attrs)
    if m:
        channel = int(m.group(1))
    pairs = None
    m = _PAIRS_RE.search(attrs)
    if m:
        pairs = tuple((int(a), int(b))
                      for a, b in _PAIR_RE.findall(m.group(1)))
    groups = None
    m = _GROUPS_RE.search(attrs)
    if m:
        groups = tuple(
            tuple(int(x) for x in g.replace(" ", "").split(",") if x)
            for g in _GROUP_RE.findall(m.group(1)))
    else:
        m = _IOTA_RE.search(attrs)
        if m:
            lhs = tuple(int(x) for x in m.group("lhs").split(","))
            dims = tuple(int(x) for x in m.group("dims").split(","))
            perm = (tuple(int(x) for x in m.group("perm").split(","))
                    if m.group("perm") else None)
            groups = _iota_replica_groups(lhs, dims, perm)
    return channel, pairs, groups


def _async_result(operand_shapes: tuple[Shape, ...],
                  result_shapes: tuple[Shape, ...]) -> tuple[Shape, ...]:
    """A ``-start`` op's tuple result aliases its operands in the leading
    elements; the true collective output is the remainder. Counting the
    whole tuple double-counts the operand into result bytes."""
    k = len(operand_shapes)
    if len(result_shapes) > k and result_shapes[:k] == operand_shapes:
        return result_shapes[k:]
    return result_shapes


_PALLAS_RE = re.compile(
    r"%(?P<name>[A-Za-z_]\w*?)(?:\.\d+)? = .*"
    r"custom_call_target=\"tpu_custom_call\"")


def pallas_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas TPU kernels (``tpu_custom_call``s) compiled
    into an HLO module — the ``name=`` each ``pallas_call`` was given.
    Empty for a program compiled for the CPU, where the kernels run in
    interpret mode."""
    return {m.group("name") for m in _PALLAS_RE.finditer(hlo_text)}


def lift_hlo(hlo_text: str) -> "CollectiveGraph":
    """Parse optimized HLO text into a :class:`CollectiveGraph`."""
    instructions: dict[str, Instruction] = {}
    # (name, base_kind, async, operand segs, result shapes, attrs)
    pending: list[tuple] = []
    for line in hlo_text.splitlines():
        m = _LINE_RE.match(line)
        if not m:
            continue
        name, rest = m.group("name"), m.group("rest")
        # result type: balanced tuple or a single space-free token
        if rest.startswith("("):
            inner, idx = _scan_balanced(rest, 0)
            type_str = "(" + inner + ")"
        else:
            idx = rest.find(" ")
            if idx < 0:
                continue
            type_str = rest[:idx]
        tail = rest[idx:].lstrip()
        om = re.match(r"([\w\-]+)\(", tail)
        if not om:
            continue
        op = om.group(1)
        operand_str, end = _scan_balanced(tail, om.end() - 1)
        attrs = tail[end:]
        result_shapes = parse_shapes(type_str)
        operand_names = tuple(_NAME_RE.findall(operand_str))
        instructions[name] = Instruction(name, op, result_shapes,
                                         operand_names)
        base = op
        is_async = False
        for sfx in ("-start", "-done"):
            if op.endswith(sfx):
                base = op[:-len(sfx)]
                is_async = True
        if base not in COLLECTIVE_OPS or op.endswith("-done"):
            continue  # -done pairs with its -start: count once
        pending.append((name, base, is_async, _split_top(operand_str),
                        result_shapes, attrs))
    # Second pass: resolve operand shapes — inline types preferred,
    # symbol table for bare %refs (synthetic/older HLO spellings).
    collectives = []
    for name, kind, is_async, segs, result_shapes, attrs in pending:
        op_names, op_shapes = [], []
        for seg in segs:
            nm = _NAME_RE.search(seg)
            if nm:
                op_names.append(nm.group(1))
            inline = parse_shapes(seg)
            if inline:
                op_shapes.extend(inline)
            elif nm and nm.group(1) in instructions:
                op_shapes.extend(instructions[nm.group(1)].result_shapes)
        operand_shapes = tuple(op_shapes)
        if is_async:
            result_shapes = _async_result(operand_shapes, result_shapes)
        channel, pairs, groups = _parse_attrs(attrs)
        collectives.append(CollectiveOp(
            kind=kind, name=name, operand_names=tuple(op_names),
            operand_shapes=operand_shapes, result_shapes=result_shapes,
            replica_groups=groups, channel_id=channel,
            source_target_pairs=pairs, asynchronous=is_async))
    return CollectiveGraph(tuple(collectives), instructions)


@dataclass
class CollectiveGraph:
    """All collectives in one HLO module, plus the full symbol table."""
    collectives: tuple[CollectiveOp, ...]
    instructions: dict[str, Instruction] = field(default_factory=dict)

    def ops(self, kind: str | None = None) -> tuple[CollectiveOp, ...]:
        if kind is None:
            return self.collectives
        return tuple(op for op in self.collectives if op.kind == kind)

    def by_kind(self) -> dict:
        """kind -> (count, operand bytes, result bytes) — the aggregate
        view ``CollectiveStats`` used to be."""
        out: dict[str, tuple[int, int, int]] = {}
        for op in self.collectives:
            c, ob, rb = out.get(op.kind, (0, 0, 0))
            out[op.kind] = (c + 1, ob + op.operand_bytes,
                            rb + op.result_bytes)
        return out

    @property
    def total_operand_bytes(self) -> int:
        return sum(op.operand_bytes for op in self.collectives)

    @property
    def total_result_bytes(self) -> int:
        return sum(op.result_bytes for op in self.collectives)

    @property
    def total_count(self) -> int:
        return len(self.collectives)

    def signature(self) -> tuple:
        """Order-insensitive structural identity of the collective set
        (names and channel ids ignored — they vary across compiles)."""
        return tuple(sorted(op.signature() for op in self.collectives))

    def consumers(self) -> dict[str, tuple[Instruction, ...]]:
        """instruction name -> instructions that take it as an operand."""
        out: dict[str, list[Instruction]] = {}
        for instr in self.instructions.values():
            for ref in instr.operand_names:
                out.setdefault(ref, []).append(instr)
        return {k: tuple(v) for k, v in out.items()}

    def downstream(self, names, depth: int = 3) -> tuple[Instruction, ...]:
        """Instructions reachable from ``names`` within ``depth`` hops of
        the def-use graph (used by the f32-intermediate rule)."""
        cons = self.consumers()
        seen: dict[str, Instruction] = {}
        frontier = list(names)
        for _ in range(depth):
            nxt = []
            for n in frontier:
                for instr in cons.get(n, ()):
                    if instr.name not in seen:
                        seen[instr.name] = instr
                        nxt.append(instr.name)
            frontier = nxt
        return tuple(seen.values())

    def summary(self) -> str:
        lines = []
        for k, (c, ob, rb) in sorted(self.by_kind().items()):
            lines.append(f"{k:20s} n={c:4d} operand={ob / 1e6:10.2f}MB "
                         f"result={rb / 1e6:10.2f}MB")
        lines.append(f"{'TOTAL':20s} n={self.total_count:4d} "
                     f"operand={self.total_operand_bytes / 1e6:10.2f}MB "
                     f"result={self.total_result_bytes / 1e6:10.2f}MB")
        return "\n".join(lines)
