"""Trace reduction: a profiler trace (``.xplane.pb``) -> device busy and
idle time, per-kind op time, exposed collective time, and a breakdown.

The device planes (``/device:TPU:<n>``) hold the ops the chip ran on
their ``XLA Ops`` line, each named by its HLO instruction's text, and
async transfers in flight on ``Async XLA Ops``; the kind of each op
comes from the step's optimized HLO (``hlo_kinds``).  The host planes
hold the harness's own
``TraceAnnotation`` spans (``bench.window`` around the measured window,
``bench.next``, ``bench.dispatch`` and ``bench.block`` inside it).  All
times are reduced over the ``bench.window`` span, on the trace's common
clock, and averaged over the cell's chips.

``Trace`` works on plain tuples, so it can be tested on events built by
hand; ``load`` reads them from a trace directory with nothing but JAX.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str               # the HLO instruction's name
    start: float            # ns
    end: float              # ns
    kind: str = "other"     # hlo_kinds' class of the instruction
    label: str = ""         # name, kind and result shape, for breakdowns


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Trace:
    """Device ops per chip and the harness's host spans, over a window."""

    def __init__(self, devices: list, spans: list, window=None):
        self.devices = devices          # [[Op, ...] per chip]
        self.spans = spans              # [(name, start, end)] host spans
        if window is None:
            w = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
            if not w:
                raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
            window = w[0]
        self.lo, self.hi = window

    def _ops(self, chip: int, pred=None) -> list:
        """(start, end) of a chip's ops that match ``pred`` (default: the
        ops that occupy the chip, not async transfer spans)."""
        pred = pred or is_device_op
        return [(o.start, o.end) for o in self.devices[chip] if pred(o)]

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self, pred=None) -> float:
        """Mean over chips of the union of matching ops' intervals."""
        return sum(covered(clip(union(self._ops(c, pred)), self.lo,
                                self.hi))
                   for c in range(len(self.devices))) \
            / len(self.devices) / 1e9

    def op_s(self, pred) -> float:
        """Mean over chips of the summed durations of matching ops."""
        return sum(covered(clip(self._ops(c, pred), self.lo, self.hi))
                   for c in range(len(self.devices))) \
            / len(self.devices) / 1e9

    def count(self, pred) -> float:
        """Mean over chips of the number of matching ops in the window."""
        return sum(1 for c in range(len(self.devices))
                   for o in self.devices[c]
                   if pred(o) and self.lo <= o.start < self.hi) \
            / len(self.devices)

    def exposed_s(self, is_collective) -> float:
        """Mean over chips of the time a collective runs while no other
        op runs on that chip."""
        total = 0.0
        for c in range(len(self.devices)):
            coll = clip(union(self._ops(c, is_collective)), self.lo,
                        self.hi)
            comp = union(self._ops(c, lambda o: is_device_op(o)
                                   and not is_collective(o)))
            total += covered(subtract(coll, comp))
        return total / len(self.devices) / 1e9

    def idle_gaps(self, chip: int = 0) -> list:
        """[(start, end)] of the window's stretches with no op on a chip."""
        busy = clip(union(self._ops(chip)), self.lo, self.hi)
        return subtract([(self.lo, self.hi)], busy)

    def host_label(self, s: float, e: float) -> str:
        """The harness span that overlaps [s, e) most, or 'other'."""
        best, label = 0.0, "other"
        for n, hs, he in self.spans:
            if n == WINDOW_SPAN:
                continue
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, n
        return label

    def breakdown(self, top: int = 10) -> dict:
        """Top device ops by time (by name, mean over chips, seconds) and
        the longest idle gaps of chip 0 by what the host was doing."""
        per = {}
        for c in range(len(self.devices)):
            for o in self.devices[c]:
                s, e = max(o.start, self.lo), min(o.end, self.hi)
                if e > s and is_device_op(o):
                    key = o.label or o.name
                    per[key] = per.get(key, 0.0) + (e - s)
        n = len(self.devices)
        ops = sorted(((k, v / n / 1e9) for k, v in per.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_label(s, e), (e - s) / 1e9]
                              for s, e in gaps]}


def _find(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def hlo_kinds(hlo_text: str) -> dict:
    """Class of each top-level instruction of a compiled program's
    optimized HLO (``Compiled.as_text()``), by instruction name:

    ``conv_fwd``    the fused Pallas conv forward (a custom call whose
                    name holds ``conv_fused``);
    ``conv_grad``   a convolution over spatial dimensions that JAX made by
                    transposing one (``transpose(`` in its op_name, not a
                    ``dot_general``), alone or inside a fusion: the conv
                    backward's input and weight gradients;
    ``collective``  an all-reduce, all-gather, reduce-scatter,
                    collective-permute or all-to-all (and their async
                    start and done halves);
    ``other``       everything else.
    """
    comps, calls, grads = {}, {}, set()
    entry, comp = None, None
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(")
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(2)
            if head.group(1):
                entry = comp
            comps[comp] = []
            continue
        m = inst.match(line)
        if not m or comp is None:
            continue
        name, shape, opcode = m.groups()
        comps[comp].append((name, shape, opcode))
        calls[name] = re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", line)
        if opcode == "convolution":
            labels = re.search(r"dim_labels=([\w]+)_", line)
            op_name = re.search(r'op_name="([^"]*)"', line)
            op_name = op_name.group(1) if op_name else ""
            spatial = labels is not None and any(
                ch.isdigit() for ch in labels.group(1))
            if spatial and "transpose(" in op_name and \
                    "dot_general" not in op_name:
                grads.add(name)

    def has_grad(name, seen=()):
        if name in grads:
            return True
        return any(has_grad(i, seen + (c,))
                   for c in calls.get(name, []) if c not in seen
                   for i, _, _ in comps.get(c, []))

    kinds = {}
    for name, shape, opcode in comps.get(entry, []):
        if opcode == "custom-call" and "conv_fused" in name:
            kinds[name] = "conv_fwd"
        elif any(opcode.startswith(c) for c in COLLECTIVES):
            kinds[name] = "collective"
        elif has_grad(name):
            kinds[name] = "conv_grad"
        else:
            kinds[name] = "other"
    return kinds


def _inst(event_name: str) -> tuple:
    """'%fusion.1 = f32[8]{0} fusion(...' -> ('fusion.1', 'f32[8]{0}')."""
    m = re.match(r"^%?([\w.\-]+)(?: = (.*?) [\w\-]+\()?", event_name)
    return (m.group(1), m.group(2) or "") if m else (event_name, "")


def _op(ev_name, start, end, kinds) -> Op:
    name, shape = _inst(ev_name)
    kind = kinds.get(name, "other")
    if kind == "other" and any(name.startswith(c) for c in COLLECTIVES):
        kind = "collective"
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return Op(name, start, end, kind, f"{name} {kind} {shape}"[:120])


def load(trace_dir, chips: int, hlo_text: str = "") -> Trace:
    """Read the trace under ``trace_dir``: the first ``chips`` device
    planes' ops (``XLA Ops``, plus the collectives of ``Async XLA Ops``,
    whose spans are transfers in flight), classed by ``hlo_kinds`` of the
    step's HLO, and every host span whose name starts with ``bench.``."""
    from jax.profiler import ProfileData
    kinds = hlo_kinds(hlo_text)
    data = ProfileData.from_file(str(_find(trace_dir)))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for ev in line.events:
                    op = _op(ev.name, ev.start_ns, ev.end_ns, kinds)
                    if line.name == OPS_LINE or op.kind == "collective":
                        ops.append(dataclasses.replace(
                            op, kind=op.kind if line.name == OPS_LINE
                            else "collective_async"))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))

    def chip_no(name):
        tail = name.rsplit(":", 1)[-1]
        return int(tail) if tail.isdigit() else 0
    ordered = [devices[k] for k in sorted(devices, key=chip_no)][:chips]
    if not any(ordered):
        # backends whose ops have no device plane (the CPU) are read
        # from the host threads that ran them
        ordered = [_host_ops(data, kinds)]
    return Trace(ordered, spans)


def _host_ops(data, kinds) -> list:
    ops = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if any(k == "hlo_op" for k, _ in ev.stats):
                    ops.append(_op(ev.name, ev.start_ns, ev.end_ns, kinds))
    return ops


def profile_options():
    """Host spans and device ops; no Python call tracing."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


# --- kinds of device op ---------------------------------------------------

def is_conv_fwd_kernel(o: Op) -> bool:
    return o.kind == "conv_fwd"


def is_conv_grad(o: Op) -> bool:
    return o.kind == "conv_grad"


def is_collective(o: Op) -> bool:
    return o.kind in ("collective", "collective_async")


def is_device_op(o: Op) -> bool:
    """Ops that occupy the chip's cores (async transfer spans do not)."""
    return o.kind != "collective_async"
