"""Join the device trace to the program's own names for its work.

The program wraps its phases and the dense path's E-wide index operations
in `jax.named_scope`s from one closed list, `repro.core.engine.SCOPES`.
The compiler keeps the scope path in each instruction's metadata, as in
`op_name="jit(<lambda>)/while/body/closed_call/vmap(phase_b)/
gather_src_spiked/gather"`, and the device trace names each op by its HLO
instruction (`trace_reduce.Op.name`).  So the compiled run program's text
(`compiled.as_text()`) maps an op of the trace to a scope:

- an instruction's scope is the innermost name of SCOPES in its op_name;
- a fusion whose own metadata names no scope takes the scope of the
  gather, scatter or custom-call at the root of its fused computation,
  recursing into nested fusions: XLA may leave the fusion itself without
  metadata, as it does the segment-sum's scatter on the TPU;
- failing that, the one scope its fused instructions name, constants
  left out (the compiler shares one constant among scopes): the TPU
  compiler's own index checks of the segment-sum carry no metadata at
  their root but keep it on the ops they were made from.

A program that predates the scopes (no `engine.SCOPES`) gives an empty
map, and every reader of a scope then finds nothing.

The program also names its Pallas kernels (`pallas_call(..., name=...)`),
so a kernel's instruction, and its events in the trace, carry the
kernel's name (`%stdp_ltp.2`): `kernel_by_name` reads it, where
`trace_reduce.kernel_of` tells the kernels apart by their results.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from chip_bench import trace_reduce


def names() -> Tuple[str, ...]:
    """The program's scope names, or () for a program without them."""
    try:
        from repro.core.engine import SCOPES
    except ImportError:
        return ()
    return tuple(SCOPES)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?(\S+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
HEROES = ("gather", "scatter", "custom-call")


class _Instr:
    __slots__ = ("opcode", "scope", "calls", "root")

    def __init__(self, text: str, root: bool, scopes: Tuple[str, ...]):
        m = _OPCODE.search(text)
        self.opcode = m.group(1) if m else ""
        m = _OP_NAME.search(text)
        self.scope = innermost(m.group(1), scopes) if m else None
        m = _CALLS.search(text) if self.opcode == "fusion" else None
        self.calls = m.group(1) if m else None
        self.root = root


def innermost(op_name: str, scopes: Tuple[str, ...]) -> Optional[str]:
    """The last name of `scopes` in an op_name path, or None."""
    best, at = None, -1
    for s in scopes:
        for m in re.finditer(rf"(?<![\w]){re.escape(s)}(?![\w])", op_name):
            if m.start() > at:
                best, at = s, m.start()
    return best


def of_instructions(hlo_text: str,
                    scopes: Optional[Tuple[str, ...]] = None
                    ) -> Dict[str, str]:
    """{instruction name: scope} for every instruction of the compiled
    module whose scope is known; instructions of no scope are left out."""
    scopes = names() if scopes is None else scopes
    if not scopes or not hlo_text:
        return {}
    comps: Dict[str, List[Tuple[str, _Instr]]] = {}
    current: Optional[List[Tuple[str, _Instr]]] = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = comps.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            current.append((m.group(2), _Instr(m.group(3), bool(m.group(1)),
                                               scopes)))

    def root_scope(comp: str) -> Optional[str]:
        for _, ins in comps.get(comp, ()):
            if not ins.root:
                continue
            if ins.opcode == "fusion" and ins.calls:
                return ins.scope or root_scope(ins.calls)
            return ins.scope if ins.opcode in HEROES else None
        return None

    def named_inside(comp: str) -> Optional[str]:
        found = {ins.scope for _, ins in comps.get(comp, ())
                 if ins.scope and ins.opcode != "constant"}
        return found.pop() if len(found) == 1 else None

    out: Dict[str, str] = {}
    for body in comps.values():
        for name, ins in body:
            s = ins.scope
            if s is None and ins.opcode == "fusion" and ins.calls:
                s = root_scope(ins.calls) or named_inside(ins.calls)
            if s is not None:
                out[name] = s
    return out


def kernel_by_name(op: trace_reduce.Op) -> Optional[str]:
    """The Pallas kernel (of trace_reduce.KERNELS) the operation's
    instruction is named after, or None."""
    if 'custom_call_target="tpu_custom_call"' not in op.text:
        return None
    base = re.sub(r"\.\d+$", "", op.name)
    return base if base in trace_reduce.KERNELS else None


def by_scope(trace: trace_reduce.Reduction,
             scope_of: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per device of the window's operations in each scope;
    "none" holds the operations of no scope."""
    out: Dict[str, float] = {}
    for o in trace.ops:
        k = scope_of.get(o.name, "none")
        out[k] = out.get(k, 0.0) + o.dur_ns / 1e9 / trace.n_devices
    return out
