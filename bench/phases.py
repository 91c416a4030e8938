"""Phases of the train step on the device, and the program's loader spans
on the host, read from a traced window.

The program traces its step under three named scopes (``core/steps.py``):
``train.loss``, ``train.update`` and ``train.exchange``.  They reach each
instruction's ``op_name`` metadata in the compiled HLO, where a path
component ``jvp(train.loss)`` marks the forward and
``transpose(jvp(train.loss))`` the backward.  ``hlo_phases`` classes each
op of the optimized HLO by them:

``forward``   the loss and its forward pass;
``backward``  the gradients (the transposed forward);
``update``    the unscale, the optimizer's update and the skip select;
``exchange``  the cross-replica averaging: collectives and their
              scaling and casts;
``other``     an op that no scoped op leads to or from, or every op of a
              program that has no scopes.

The device ops of a trace are named by instruction; the HLO they ran comes
from the profiler's own record of each program (the metadata plane's HLO
protos, ``hlo_texts``), so the readers need nothing the run does not keep.
The loader's spans (``repro.data.*``, ``data/pipeline.py``) are host
events on the loader's thread; ``Window.span_ms`` reads them over the
same window.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

import xplane

TRACES = Path(__file__).resolve().parent / ".cache" / "trace"
LOSS, UPDATE, EXCHANGE = "train.loss", "train.update", "train.exchange"
# the phases in the order a step runs them
ORDER = ("other", "forward", "backward", "update", "exchange")
SPAN_PREFIX = "repro."


def phase_of(op_name: str) -> str:
    """The phase an ``op_name`` path names: its innermost component that
    names one, or ``other``."""
    phase = "other"
    for part in op_name.split("/"):
        if EXCHANGE in part:
            phase = "exchange"
        elif UPDATE in part:
            phase = "update"
        elif LOSS in part:
            phase = "backward" if "transpose(" in part else "forward"
    return phase


def hlo_phases(hlo_text: str) -> dict:
    """Phase of each instruction that runs as an op of its own (those of
    the ENTRY computation and of loop bodies and branches, not those
    inside fusions) in an optimized HLO module's text, by name.

    An instruction takes the phase its own ``op_name`` names; a fusion
    whose root names none (a root that only reshapes the new state) takes
    the latest phase named inside it.  XLA gives a fusion its root's
    metadata, so a fusion that spans phases (the optimizer's update fused
    into a weight gradient) counts whole in its root's.  An op with no
    phase yet (XLA's layout copies) takes the latest phase of the values
    it is computed from, and failing that (a copy of an argument), the
    earliest phase among its users.  "Latest" and "earliest" follow
    ``PHASES``; the text lists each computation's instructions operands
    first."""
    comps, runs = {}, {}
    comp = entry = None
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? [\w\-]+\(([^)]*)\)")
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(2)
            if head.group(1):
                entry = comp
            comps[comp] = {}
            continue
        m = inst.match(line)
        if not m or comp is None:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        comps[comp][m.group(1)] = (
            phase_of(op_name.group(1)) if op_name else "other",
            re.findall(r"calls=%([\w.\-]+)", line),
            re.findall(r"%([\w.\-]+)", m.group(2)))
        # loop bodies and branches run their instructions as ops
        runs.setdefault(comp, []).extend(re.findall(
            r"(?:body|condition|true_computation|false_computation)="
            r"%([\w.\-]+)", line))
        branches = re.search(r"branch_computations=\{([^}]*)\}", line)
        if branches:
            runs[comp] += re.findall(r"%([\w.\-]+)", branches.group(1))

    def latest(phases):
        return max(phases, key=ORDER.index, default="other")

    def named(comp, name):
        phase, calls, _ = comps[comp][name]
        if phase == "other":
            phase = latest(named(f, n) for f in calls if f in comps
                           for n in comps[f])
        return phase

    out, pending = {}, [entry]
    while pending:
        c = pending.pop()
        if c not in comps or any(n in out for n in comps[c]):
            continue
        pending += runs.get(c, [])
        phase, users = {}, {}
        for name, (_, _, operands) in comps[c].items():
            own = named(c, name)
            phase[name] = own if own != "other" else latest(
                phase.get(o, "other") for o in operands)
            for o in operands:
                users.setdefault(o, []).append(name)
        for name in reversed(list(comps[c])):
            if phase[name] == "other":
                used = [phase[u] for u in users.get(name, [])
                        if phase[u] != "other"]
                if used:
                    phase[name] = min(used, key=ORDER.index)
        out.update(phase)
    return out


# --- reading a recorded window ---------------------------------------------

def _varint(b, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a protobuf message's wire bytes; a
    length-delimited value is a memoryview, a fixed-width one is skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in a trace")
        yield key >> 3, v


def hlo_texts(path) -> dict:
    """The HLO text of each program the profiler recorded, by the name its
    ``XLA Modules`` events carry: the ``/host:metadata`` plane of the
    XSpace holds one event metadata per program (field numbers of
    ``tsl/profiler/protobuf/xplane.proto``), each with an ``Hlo Proto``
    stat whose first field is the HloModuleProto, which jaxlib's
    ``HloModule`` prints as text."""
    from jax._src.lib import _jax
    data = memoryview(Path(path).read_bytes())
    out = {}
    for field, plane in _fields(data):
        if field != 1:                                   # XSpace.planes
            continue
        parts = list(_fields(plane))
        if bytes(next((v for f, v in parts if f == 2), b"")) \
                != b"/host:metadata":
            continue
        hlo_stat = None
        for f, v in parts:                               # stat_metadata
            if f == 5:
                md = dict(_fields(dict(_fields(v))[2]))
                if bytes(md.get(2, b"")) == b"Hlo Proto":
                    hlo_stat = md[1]
        for f, v in parts:                               # event_metadata
            if f != 4:
                continue
            md = list(_fields(dict(_fields(v))[2]))
            name = bytes(next((x for g, x in md if g == 2), b"")).decode()
            for g, x in md:
                stat = dict(_fields(x)) if g == 5 else {}
                if stat.get(1) == hlo_stat and 6 in stat:
                    module = dict(_fields(stat[6]))[1]
                    out[name] = _jax.HloModule.from_serialized_hlo_module_proto(
                        bytes(module)).to_string()
    return out


class Window:
    """The step's device time by phase (seconds, mean over chips) and the
    program's host spans, over a traced window ``[lo, hi)``."""

    def __init__(self, path, lo: float, hi: float, chips: int):
        from jax.profiler import ProfileData
        self.lo, self.hi = lo, hi
        maps = {name: hlo_phases(text)
                for name, text in hlo_texts(path).items() if LOSS in text}
        by_base = {}
        for name, m in maps.items():
            by_base.setdefault(name.split("(")[0], []).append(m)
        data = ProfileData.from_file(str(path))
        self.spans = []         # (name, start, end) of the repro.* spans
        self.is_run = False     # the run's own bench.window is in the file
        devices = {}
        for plane in data.planes:
            if plane.name.startswith("/device:") and \
                    not plane.name.startswith("/device:CUSTOM"):
                devices[plane.name] = self._device_ops(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            self.spans.append((ev.name, ev.start_ns,
                                               ev.end_ns))
                        elif ev.name == xplane.WINDOW_SPAN and \
                                (ev.start_ns, ev.end_ns) == (lo, hi):
                            self.is_run = True
        if not any(devices.values()):
            # the CPU runs its ops on host threads, each naming its program
            devices = {"host": [
                (dict(ev.stats).get("hlo_module", ""),
                 dict(ev.stats)["hlo_op"], ev.start_ns, ev.end_ns)
                for plane in data.planes if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if "hlo_op" in dict(ev.stats)]}
        chip_order = sorted(devices, key=lambda k: int(k.rsplit(":", 1)[-1])
                            if k.rsplit(":", 1)[-1].isdigit() else 0)
        self.has_phases = bool(maps)
        self.phase_s = {}
        for key in chip_order[:chips]:
            for module, op, s, e in devices[key]:
                m = maps.get(module)
                if m is None:
                    same = by_base.get(module.split("(")[0], [])
                    if len(same) != 1:
                        continue            # not the step's program
                    m = same[0]
                phase = m.get(op, "other")
                dur = min(e, hi) - max(s, lo)
                if dur > 0:
                    self.phase_s[phase] = self.phase_s.get(phase, 0.0) + dur
        n = max(1, min(chips, len(chip_order)))
        self.phase_s = {k: v / n / 1e9 for k, v in self.phase_s.items()}

    @staticmethod
    def _device_ops(plane) -> list:
        """(program, instruction, start, end) of a device plane's ops,
        each in the program whose ``XLA Modules`` event holds its start."""
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        out = []
        for ev in lines.get(xplane.OPS_LINE, []):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            module = mods[i][2] if i >= 0 and ev.start_ns < mods[i][1] \
                else ""
            out.append((module, xplane._inst(ev.name)[0], ev.start_ns,
                        ev.end_ns))
        return out

    def span_ms(self, name: str):
        """Mean milliseconds of the spans called ``name`` that start in
        the window, or None when there are none."""
        durs = [e - s for n, s, e in self.spans
                if n == name and self.lo <= s < self.hi]
        return sum(durs) / len(durs) / 1e6 if durs else None


_WINDOWS: dict = {}


def window(ctx):
    """The ``Window`` of a traced run's context (``run.py``): the newest
    trace the harness recorded whose ``bench.window`` span is the run's
    own.  None for an untraced run."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    key = (tr.lo, tr.hi, ctx["chips"])
    if key not in _WINDOWS:
        files = sorted(TRACES.glob("*/**/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        w = Window(files[-1], tr.lo, tr.hi, ctx["chips"]) if files else None
        _WINDOWS.clear()                # one run, one window
        _WINDOWS[key] = w if w is not None and w.is_run else None
    return _WINDOWS[key]


def phase_ms(ctx, phase: str):
    """Device milliseconds per step in ``phase``, mean over chips; None
    when the program traced no phase scopes (a program older than them)."""
    w = window(ctx)
    if w is None or not w.has_phases or not ctx["steps"]:
        return None
    return 1e3 * w.phase_s.get(phase, 0.0) / ctx["steps"]
