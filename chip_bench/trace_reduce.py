"""Reduce a profiler trace (`.xplane.pb`) of the window to what the metrics
read.

The run writes host spans with `jax.profiler.TraceAnnotation`: `window`
around the whole window and `chunk` around each chunk.  The reduction
takes from the trace:

- the window: the `window` span on the host plane;
- per device plane (`/device:TPU:<i>`), the operations on its `XLA Ops`
  line that fall inside the window;
- busy time: the union of those operations' intervals, averaged over the
  devices the cell uses; idle gaps: the rest of the window on the first
  device, each named by the innermost host span around its middle;
- for each operation its HLO text, which the TPU trace gives as the
  event's name, and from which `kind` tells the collectives, the Pallas
  kernels and the gathers and scatters apart;
- per device, the time of operations of one kind that no other operation
  on that device covers (`exposed`): for the collectives, the time the
  device waits on the exchange.

What the text shows (read by hand on a 4x4 trace, chip_bench/testdata):
the Pallas kernels are `custom-call`s with
`custom_call_target="tpu_custom_call"`, named after the vmap they sit in
(`%vmap__.6`), not after their kernel functions, so they are told apart
by their results: the Izhikevich kernel gives (f32, f32, s32) blocks, the
arrival kernel three f32 blocks, the LTP kernel one f32 block.  XLA's
gathers and scatters on the TPU are fusions of `kind=kCustom` over an s32
index operand (the E-wide `last_post[tgt]`, `spiked[tgt]`,
`spiked_src[src]` and the scatter-add of `segment_sum`); the text does not
say which of the two a fusion is.

On four chips (chip_bench/testdata/trace_4x4_h4.xplane.pb, the halo wire
under the pipelined schedule) each device plane holds the same program's
ops, and the exchange's `ppermute`s are the HLO's async
`collective-permute-start` and `collective-permute-done`, one pair per
halo offset and step on each chip's `XLA Ops` line: the start launches the
transfer (1-2 us there), the done waits for it (a few ns where the
transfer is over), and the compute between them is what hides it.  The
transfers themselves, with the async slices and copies, are on the
first chip's `Async XLA Ops` line, which the reduction does not read.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
# host spans that name idle gaps, from the benchmark's own files
SPANS = ("window", "chunk")
# the Pallas kernels, by the element types of their results
KERNELS = {"izhikevich": ("f32", "f32", "s32"),
           "stdp_arrival": ("f32", "f32", "f32"),
           "stdp_ltp": ("f32",)}


@dataclasses.dataclass
class Op:
    text: str          # the HLO instruction, as the trace names the event
    start_ns: float
    dur_ns: float
    device: int

    @property
    def name(self) -> str:
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_CALL = re.compile(r"^%\S+ = (.*?) custom-call\(")
# an HLO collective or either half of its async form, as the opcode after
# the result's type
_COLLECTIVE = re.compile(r"^%\S+ = .*? (all-gather|collective-permute|"
                         r"all-reduce|all-to-all|reduce-scatter)"
                         r"(-start|-done)?\(")
_CUSTOM_FUSION = re.compile(r" fusion\((.*)\), kind=kCustom")


def kernel_of(op: Op) -> Optional[str]:
    """Which Pallas kernel the operation is, or None."""
    m = _CALL.match(op.text)
    if not m or 'custom_call_target="tpu_custom_call"' not in op.text:
        return None
    types = tuple(re.findall(r"\b([a-z]+\d*)\[", m.group(1)))
    for kernel, want in KERNELS.items():
        if types == want:
            return kernel
    return None


def kind(op: Op) -> str:
    """collective, kernel:<name>, gather_scatter or other."""
    if _COLLECTIVE.match(op.text):
        return "collective"
    k = kernel_of(op)
    if k:
        return "kernel:" + k
    m = _CUSTOM_FUSION.search(op.text)
    if m and "s32[" in m.group(1):
        return "gather_scatter"
    return "other"


def is_collective(op: Op) -> bool:
    return kind(op) == "collective"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two unions of intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over the devices used
    ops: List[Op]                 # inside the window, all devices used
    gaps: List[Tuple[str, float]]  # idle gaps of the first device
    n_devices: int
    busy_per_device: List[float]  # seconds, in device order

    def seconds(self, pred) -> float:
        """Device seconds of the operations `pred` accepts, per device."""
        return sum(o.dur_ns for o in self.ops if pred(o)) / 1e9 \
            / self.n_devices

    def exposed_per_device(self, pred) -> Dict[int, float]:
        """{device: seconds} in which an operation `pred` accepts runs on
        that device and no other operation does."""
        out = {}
        for d in sorted({o.device for o in self.ops}):
            mine = [o for o in self.ops if o.device == d]
            sel = _union([(o.start_ns, o.end_ns) for o in mine if pred(o)])
            rest = _union([(o.start_ns, o.end_ns) for o in mine
                           if not pred(o)])
            out[d] = (sum(b - a for a, b in sel) - _overlap(sel, rest)) / 1e9
        return out

    def exposed(self, pred) -> float:
        """`exposed_per_device`, averaged over the devices used."""
        return sum(self.exposed_per_device(pred).values()) / self.n_devices

    def calls(self, pred) -> float:
        """Operations `pred` accepts, per device."""
        return sum(1 for o in self.ops if pred(o)) / self.n_devices

    def breakdown(self, top: int = 10) -> dict:
        per: Dict[str, float] = {}
        for o in self.ops:
            label = f"{o.name} ({kind(o)})"
            per[label] = per.get(label, 0.0) + o.dur_ns / 1e9
        by_span: Dict[str, float] = {}
        for name, s in self.gaps:
            by_span[name] = by_span.get(name, 0.0) + s
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / self.n_devices] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(path: str, n_devices: int) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices: Dict[int, list] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [
                line for line in plane.lines if line.name == OPS_LINE]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    used = sorted(devices)[:n_devices]
    if len(used) < n_devices:
        raise ValueError(f"the trace has {len(used)} TPU device planes, "
                         f"the cell uses {n_devices}")
    ops: List[Op] = []
    busy = []
    for d in used:
        mine = []
        for line in devices[d]:
            for e in line.events:
                if e.start_ns >= w0 and e.start_ns + e.duration_ns <= w1:
                    mine.append(Op(text=e.name, start_ns=e.start_ns,
                                   dur_ns=e.duration_ns, device=d))
        ops += mine
        busy.append(_union([(o.start_ns, o.end_ns) for o in mine]))
    busy_s = sum(b - a for u in busy for a, b in u) / 1e9 / n_devices
    gaps = []
    t = w0
    for a, b in busy[0] + [(w1, w1)]:
        if a > t:
            mid = 0.5 * (a + t)
            around = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            gaps.append((min(around)[1] if around else "none",
                         (a - t) / 1e9))
        t = max(t, b)
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy_s, ops=ops,
                     gaps=gaps, n_devices=n_devices,
                     busy_per_device=[sum(b - a for a, b in u) / 1e9
                                      for u in busy])
