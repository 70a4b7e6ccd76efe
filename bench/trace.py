"""Reduce a JAX profiler trace to the intervals the metrics read.

The harness traces the measured window with ``jax.profiler.trace`` and
writes its own host spans into the same trace with
``jax.profiler.TraceAnnotation`` (:data:`SPAN_PREFIX` + ``window``,
``dispatch``, ``block``).  :func:`load` reads the ``.xplane.pb`` with
nothing but JAX and returns a :class:`Summary`:

* per device (``/device:TPU:<n>`` planes), the operations of the
  ``XLA Ops`` line, clipped to the window and sorted into three kinds
  by what the op is, never by a Python name: ``kernel`` (a Mosaic
  custom call), ``collective`` (an exchange between chips) and
  ``glue`` (everything else XLA runs: pads, slices, copies, fusions);
* the harness's host spans, for naming the device's idle gaps.

All times are integer nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"

KERNEL, COLLECTIVE, GLUE = "kernel", "collective", "glue"
KINDS = (KERNEL, COLLECTIVE, GLUE)

#: HLO opcodes that move data between chips.
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast", "send", "recv", "ragged-all-to-all")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True, order=True)
class Op:
    start: int
    end: int
    name: str
    kind: str


#: Control-flow ops: their events enclose the ops of their bodies, so
#: they are left out and their bodies' ops stand for them.
CONTAINER_OPCODES = ("while", "conditional", "call")

# "%copy.9 = f32[512,512]{1,0:T(8,128)} copy(f32[...] %x)": the instance
# name, then the opcode as the first "word(" after the result shape.
_HLO_TEXT = re.compile(r"^%?(?P<inst>[^\s=]+) = .*?\s(?P<op>[a-z][a-z0-9-]*)\(")


def parse_op(text: str) -> tuple[str, str]:
    """``(short name, opcode)`` of a device op event, whose name is the
    HLO instruction's text; a bare instance name (``fusion.3``) gives
    its opcode from the name."""
    m = _HLO_TEXT.match(text)
    if m:
        inst, opcode = m.group("inst"), m.group("op")
    else:
        inst = text.split(" ", 1)[0].lstrip("%")
        opcode = re.sub(r"\.\d+$", "", inst)
    short = inst if inst.startswith(opcode) else f"{inst} ({opcode})"
    return short, opcode


def op_kind(text: str, opcode: str) -> str | None:
    """``kernel`` (a Mosaic custom call), ``collective`` or ``glue`` for
    one device op, from its opcode and HLO text; ``None`` for a
    control-flow op."""
    if opcode in CONTAINER_OPCODES:
        return None
    if opcode == "custom-call" and "tpu_custom_call" in text:
        return KERNEL
    if any(opcode.startswith(c) for c in COLLECTIVE_OPCODES):
        return COLLECTIVE
    return GLUE


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` cover of ``intervals``."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list[tuple[int, int]]:
    """The parts of the cover of ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Summary:
    window: tuple[int, int]
    devices: dict[int, list[Op]]
    spans: list[tuple[int, int, str]]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def intervals(self, dev: int, kinds=KINDS) -> list[tuple[int, int]]:
        return [(o.start, o.end) for o in self.devices[dev] if o.kind in kinds]

    def busy_ns(self, dev: int, kinds=KINDS) -> int:
        """Union of the device's op intervals of ``kinds``."""
        return length(self.intervals(dev, kinds))

    def summed_ns(self, dev: int, kind: str) -> int:
        """Summed durations of the device's ops of ``kind``."""
        return sum(o.end - o.start for o in self.devices[dev]
                   if o.kind == kind)

    def gaps(self, dev: int) -> list[tuple[int, int]]:
        """The window's idle intervals on ``dev``."""
        return subtract([self.window], self.intervals(dev))

    def span_at(self, t: int) -> str:
        """Name of the innermost host span around ``t`` (prefix
        stripped), or ``host`` where the harness was in none of its
        spans but the window's."""
        best = None
        for s, e, name in self.spans:
            if name != WINDOW_SPAN and s <= t < e and (
                    best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2][len(SPAN_PREFIX):] if best else "host"

    def top_ops(self, n: int = 10) -> list[list]:
        """Ops that took most device time, mean seconds per device."""
        total: dict[str, int] = {}
        for ops in self.devices.values():
            for o in ops:
                total[o.name] = total.get(o.name, 0) + o.end - o.start
        per = max(len(self.devices), 1)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / per / 1e9] for name, ns in ranked]

    def top_gaps(self, dev: int, n: int = 10) -> list[list]:
        """The longest idle gaps on ``dev``, named by the host span the
        harness was in at the gap's middle."""
        ranked = sorted(self.gaps(dev), key=lambda g: g[0] - g[1])[:n]
        return [[self.span_at((s + e) // 2), (e - s) / 1e9]
                for s, e in ranked]


def reduce(planes) -> Summary:
    """Build a :class:`Summary` from ``ProfileData.planes``."""
    devices: dict[int, list[Op]] = {}
    spans: list[tuple[int, int, str]] = []
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == _OPS_LINE:
                ops = devices.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    short, opcode = parse_op(ev.name)
                    kind = op_kind(ev.name, opcode)
                    if kind is not None:
                        start = int(ev.start_ns)
                        ops.append(Op(start, start + int(ev.duration_ns),
                                      short, kind))
            elif not m:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append((start, start + int(ev.duration_ns),
                                      ev.name))
    windows = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN!r} spans")
    if not devices:
        raise ValueError("trace holds no TPU device plane with XLA ops")
    w0, w1 = windows[0]
    clipped = {
        dev: sorted(Op(max(o.start, w0), min(o.end, w1), o.name, o.kind)
                    for o in ops if o.end > w0 and o.start < w1)
        for dev, ops in devices.items()}
    return Summary((w0, w1), clipped, sorted(spans))


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise ValueError(f"{len(found)} .xplane.pb files under {log_dir}")
    return found[0]


def load(path: str) -> Summary:
    """Reduce the ``.xplane.pb`` at ``path`` (or a gzip of one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as fh:
            return reduce(ProfileData.from_serialized_xspace(fh.read()).planes)
    return reduce(ProfileData.from_file(path).planes)
