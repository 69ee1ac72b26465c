"""Optimized-HLO cost extraction for the roofline analysis.

``compiled.cost_analysis()`` counts a ``while`` body ONCE, not times its trip
count — useless for scan-over-layers models. This module walks the optimized
HLO text instead:

  * computations are parsed into op lists,
  * ``while`` ops carry ``backend_config={"known_trip_count":{"n":...}}`` —
    body + condition costs are multiplied by it,
  * ``fusion``/``call``/``conditional`` recurse into their subcomputations
    for FLOPs; fusion byte traffic is the fusion's own operands + outputs
    (internal traffic stays in registers/VMEM),
  * ``dot`` FLOPs = 2 x prod(output shape) x prod(lhs contracting dims),
  * collective bytes = sum of operand bytes of all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute (+ ``-start`` forms),
    scaled per §Roofline conventions.

Validated against exact matmul/scan cases in tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0, "s2": 1, "u2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all", "collective-broadcast")


@dataclass
class OpCost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    per_collective: dict = field(default_factory=dict)

    def __iadd__(self, o):
        self.flops += o.flops
        self.bytes += o.bytes
        self.collective_bytes += o.collective_bytes
        for k, v in o.per_collective.items():
            self.per_collective[k] = self.per_collective.get(k, 0.0) + v
        return self

    def scaled(self, n: float) -> "OpCost":
        return OpCost(self.flops * n, self.bytes * n,
                      self.collective_bytes * n,
                      {k: v * n for k, v in self.per_collective.items()})


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _type_bytes_elems(type_str: str) -> tuple[float, float]:
    """Total (bytes, elements) for a type string (handles tuples)."""
    total_b = total_e = 0.0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1.0
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total_b += elems * _DTYPE_BYTES[dt]
        total_e += elems
    return total_b, total_e


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*))\s+"
    r"([\w\-]+)\(([^)]*)\)(.*)$")

_CALL_RE = re.compile(r"(?:calls|to_apply|condition|body|true_computation|"
                      r"false_computation|branch_computations)="
                      r"(?:\{([^}]*)\}|%?([\w.\-]+))")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _split_operands(s: str) -> list[str]:
    """Split an operand list on top-level commas only (shape dims and layout
    braces contain commas: ``f32[1,32,64]{2,1,0} %name``)."""
    parts, cur, depth = [], [], 0
    for ch in s:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def _operand_name(tok: str) -> str:
    """Operand name from either ``%name`` or ``type %name`` spellings."""
    for t in reversed(tok.split()):
        if t.startswith("%"):
            return t.lstrip("%")
    return tok.strip().lstrip("%").split(" ")[0]


@dataclass
class _Op:
    name: str
    type_str: str
    opcode: str
    operands: list
    rest: str


class HloModule:
    def __init__(self, text: str):
        self.computations: dict[str, list[_Op]] = {}
        self.entry: str | None = None
        self._parse(text)

    def _parse(self, text: str):
        cur = None
        for line in text.splitlines():
            s = line.rstrip()
            stripped = s.strip()
            if (not s.startswith((" ", "\t")) and stripped.endswith("{")
                    and "->" in stripped and "=" not in stripped.split("(")[0]):
                is_entry = stripped.startswith("ENTRY")
                head = stripped[len("ENTRY"):].strip() if is_entry else stripped
                name = re.split(r"[\s(]", head.lstrip("%"), maxsplit=1)[0]
                self.computations[name] = []
                cur = name
                if is_entry:
                    self.entry = name
                continue
            if s.strip() == "}":
                cur = None
                continue
            if cur is None:
                continue
            m = _OP_RE.match(s)
            if not m:
                continue
            name, type_str, opcode, operands, rest = m.groups()
            ops = [_operand_name(o)
                   for o in _split_operands(operands) if o.strip()]
            self.computations[cur].append(
                _Op(name, type_str, opcode, ops, rest))

    # -- cost walk -----------------------------------------------------------
    def cost(self) -> OpCost:
        if self.entry is None:
            # fall back: largest computation
            self.entry = max(self.computations, key=lambda k: len(self.computations[k]))
        self._memo: dict[tuple[str, bool], OpCost] = {}
        return self._comp_cost(self.entry, top=True)

    def _comp_cost(self, comp: str, top: bool) -> OpCost:
        key = (comp, top)
        if key in self._memo:
            return self._memo[key]
        total = OpCost()
        symtab = {op.name: op for op in self.computations.get(comp, [])}
        for op in self.computations.get(comp, []):
            total += self._op_cost(op, symtab, top)
        self._memo[key] = total
        return total

    def _called(self, op: _Op) -> list[str]:
        names = []
        for m in _CALL_RE.finditer(op.rest):
            blob = m.group(1) or m.group(2) or ""
            for nm in blob.split(","):
                nm = nm.strip().lstrip("%")
                if nm in self.computations:
                    names.append(nm)
        return names

    def _op_cost(self, op: _Op, symtab: dict, top: bool) -> OpCost:
        oc = op.opcode
        out_bytes, out_elems = _type_bytes_elems(op.type_str)

        if oc == "while":
            trips = 1
            m = _TRIP_RE.search(op.rest)
            if m:
                trips = int(m.group(1))
            inner = OpCost()
            for c in self._called(op):
                inner += self._comp_cost(c, top=False)
            return inner.scaled(trips)

        if oc == "fusion":
            inner = OpCost()
            called = self._called(op)
            for c in called:
                inner += self._comp_cost(c, top=False)
            # bytes at the fusion boundary, ALIAS/SLICE-AWARE: an operand
            # consumed only through dynamic-slice reads is charged at the
            # slice bytes (XLA reads just the window); an operand that is
            # in-place dynamic-update-slice'd (same type as the output) is
            # charged at 2x the update bytes (read+write of the window) —
            # XLA's buffer assignment aliases the rest.
            in_bytes = self._fusion_operand_bytes(op, symtab, called)
            out = out_bytes
            dus_update = self._fusion_dus_update_bytes(op, called)
            if dus_update is not None:
                out = dus_update
            return OpCost(flops=inner.flops,
                          bytes=in_bytes + out,
                          collective_bytes=inner.collective_bytes,
                          per_collective=inner.per_collective)

        if oc in ("call", "conditional", "async-start"):
            inner = OpCost()
            for c in self._called(op):
                inner += self._comp_cost(c, top=False)
            inner.bytes += out_bytes
            return inner

        base = oc.replace("-start", "") if oc.endswith("-start") else oc
        if base in COLLECTIVES:
            in_bytes = self._operand_bytes(op, symtab)
            # comm bytes on the wire: use operand bytes (spec convention)
            return OpCost(bytes=in_bytes + out_bytes,
                          collective_bytes=in_bytes,
                          per_collective={base: in_bytes})

        if oc == "dot":
            in_bytes = self._operand_bytes(op, symtab)
            k = self._contracting_elems(op, symtab)
            return OpCost(flops=2.0 * out_elems * k, bytes=in_bytes + out_bytes)

        if oc == "convolution":
            in_bytes = self._operand_bytes(op, symtab)
            # rough: 2 * out_elems * prod(kernel spatial+input feature)
            kshape = self._operand_shape(op, symtab, 1)
            k = float(np.prod(kshape)) if kshape else 1.0
            return OpCost(flops=2.0 * out_elems * max(k, 1.0) /
                          max(self._out_feature(op), 1.0),
                          bytes=in_bytes + out_bytes)

        if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "after-all", "partition-id", "replica-id"):
            return OpCost()

        if oc in ("slice", "dynamic-slice"):
            # reads only the window, not the whole operand
            return OpCost(bytes=2.0 * out_bytes)

        if oc == "dynamic-update-slice":
            # in-place window write: read+write the update, alias the rest
            upd = self._operand_shape_bytes(op, symtab, 1)
            return OpCost(bytes=2.0 * upd if upd else out_bytes)

        if oc in ("copy", "copy-start", "copy-done", "transpose", "reshape",
                  "broadcast", "concatenate", "pad", "reverse", "gather",
                  "scatter", "iota", "convert", "reduce", "select", "compare",
                  "rng", "rng-bit-generator", "sort", "all-reduce-done",
                  "all-gather-done", "collective-permute-done", "custom-call",
                  "optimization-barrier"):
            in_bytes = self._operand_bytes(op, symtab)
            flops = out_elems if oc in ("reduce", "sort") else 0.0
            return OpCost(flops=flops, bytes=in_bytes + out_bytes)

        # elementwise & everything else: 1 flop/elem, boundary bytes
        in_bytes = self._operand_bytes(op, symtab)
        return OpCost(flops=out_elems, bytes=in_bytes + out_bytes)

    # -- helpers ---------------------------------------------------------------
    _PARAM_RE = re.compile(r"^param_(\d+)")

    def _fusion_param_uses(self, called: list[str]) -> dict[int, list]:
        """param index -> [(consumer opcode, consumer out bytes)]."""
        uses: dict[int, list] = {}
        for c in called:
            for op in self.computations.get(c, []):
                ob, _ = _type_bytes_elems(op.type_str)
                for o in op.operands:
                    m = self._PARAM_RE.match(o)
                    if m:
                        uses.setdefault(int(m.group(1)), []).append(
                            (op.opcode, ob))
        return uses

    def _fusion_operand_bytes(self, op: _Op, symtab: dict,
                              called: list[str]) -> float:
        uses = self._fusion_param_uses(called)
        total = 0.0
        for i, o in enumerate(op.operands):
            src = symtab.get(o)
            if src is None:
                continue
            full, _ = _type_bytes_elems(src.type_str)
            u = uses.get(i)
            if u and all(c in ("dynamic-slice", "slice") for c, _ in u):
                total += min(full, sum(b for _, b in u))
            elif u and all(c == "dynamic-update-slice" for c, _ in u):
                total += 0.0          # aliased in-place destination
            else:
                total += full
        return total

    def _fusion_dus_update_bytes(self, op: _Op, called: list[str]):
        """If the fusion's root is an in-place dynamic-update-slice of an
        operand with the fusion's own output type, charge 2x update bytes."""
        for c in called:
            ops = self.computations.get(c, [])
            if not ops:
                continue
            root = ops[-1]
            if root.opcode == "dynamic-update-slice" and \
                    root.type_str.split("{")[0] == op.type_str.split("{")[0]:
                # update operand is index 1; look it up in the inner comp
                inner_tab = {o2.name: o2 for o2 in ops}
                upd = inner_tab.get(root.operands[1]) if len(root.operands) > 1 else None
                if upd is not None:
                    b, _ = _type_bytes_elems(upd.type_str)
                    return 2.0 * b
        return None

    def _operand_shape_bytes(self, op: _Op, symtab: dict, idx: int) -> float:
        if idx >= len(op.operands):
            return 0.0
        src = symtab.get(op.operands[idx])
        if src is None:
            return 0.0
        b, _ = _type_bytes_elems(src.type_str)
        return b

    def _operand_bytes(self, op: _Op, symtab: dict) -> float:
        total = 0.0
        for o in op.operands:
            src = symtab.get(o)
            if src is not None:
                b, _ = _type_bytes_elems(src.type_str)
                total += b
        return total

    def _operand_shape(self, op: _Op, symtab: dict, idx: int):
        if idx >= len(op.operands):
            return None
        src = symtab.get(op.operands[idx])
        if src is None:
            return None
        m = _SHAPE_RE.search(src.type_str)
        if not m:
            return None
        dims = m.group(2)
        return [int(d) for d in dims.split(",")] if dims else []

    def _out_feature(self, op: _Op) -> float:
        m = _SHAPE_RE.search(op.type_str)
        if not m or not m.group(2):
            return 1.0
        return float(m.group(2).split(",")[-1])

    def _contracting_elems(self, op: _Op, symtab: dict) -> float:
        """prod of lhs contracting dim sizes for a dot."""
        lhs_shape = self._operand_shape(op, symtab, 0)
        if lhs_shape is None:
            return 1.0
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
        if not m:
            return 1.0
        k = 1.0
        for d in m.group(1).split(","):
            if d:
                k *= lhs_shape[int(d)]
        return k


def analyze_hlo_text(text: str) -> OpCost:
    return HloModule(text).cost()


def analyze_compiled(compiled) -> dict:
    """Cost summary dict for a jax.stages.Compiled (per-device numbers)."""
    cost = analyze_hlo_text(compiled.as_text())
    xla = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    return {
        "flops_per_device": cost.flops,
        "bytes_per_device": cost.bytes,
        "collective_bytes_per_device": cost.collective_bytes,
        "per_collective": cost.per_collective,
        "xla_flops_unscaled": float(xla.get("flops", 0.0)),
        "xla_bytes_unscaled": float(xla.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
    }
