"""The program's own spans and scopes in a JAX profiler trace, reduced
beside what :func:`xplane.reduce_trace` gives, on the same clock.

Read from one ``.xplane.pb``:

* the program's host spans (``jax.profiler.TraceAnnotation`` named
  ``engine.*`` and ``train.*``, see ``repro/runtime/trace_names.py``),
  with their attributes, on the host clock;
* the device's idle intervals in the window, per device, on the host
  clock (shifted as :mod:`xplane` shifts them);
* device seconds per (module, instruction): the module is the ``XLA
  Modules`` event the op runs inside, and the instruction its full HLO
  name;
* each instruction's ``op_name`` path, from the ``tf_op`` statistic of
  its event metadata. ``ProfileData`` does not expose event metadata, so
  the few fields needed are read from the protobuf's wire format here.

The six measures at the end read these: the host time between decode
steps, the lanes a step serves, the device idle while the engine runs,
and the shares of a program's device time by named scope.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

import xplane
from scopes import scope_of
from stats import percentile

try:
    from repro.runtime import trace_names as N
except ImportError:                 # a program without spans
    N = None

PREFIXES = ("engine.", "train.")
_INSTRUCTION = re.compile(r"^%?([^ =]+)\s*=")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
# the scopes of the decode program that are the model's or the sampler's
# own work; what none of them claims is the layer scan's slicing, copying
# and write-back of the stacked pools, and whatever else has no scope
DECODE_WORK = ("embed", "norm", "attention", "kv_write", "mlp", "lm_head",
               "sample")


@dataclass(frozen=True)
class Span:
    name: str
    start: float                    # ns, host clock
    end: float
    attrs: dict


@dataclass
class ProgramTrace:
    window: tuple                   # (start, end) ns, host clock
    devices: int
    spans: list = field(default_factory=list)      # [Span] by start
    idle: list = field(default_factory=list)       # per device [(a, b)]
    op_s: dict = field(default_factory=dict)       # (module, instr) -> s
    op_path: dict = field(default_factory=dict)    # (module, instr) -> path

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def named(self, name: str) -> list:
        """Spans called ``name`` that start inside the window."""
        w0, w1 = self.window
        return [s for s in self.spans
                if s.name == name and w0 <= s.start < w1]

    def idle_by_span(self) -> dict:
        """Idle device seconds, mean over devices, by the innermost program
        span open at the time (``None``: no span open)."""
        out = defaultdict(float)
        for ivs in self.idle:
            for secs, open_ in _sweep(self.spans, ivs):
                inner = max(open_, key=lambda s: s.start, default=None)
                out[inner.name if inner else None] += secs / self.devices
        return dict(out)

    def module_s(self, module: str) -> dict:
        """Device seconds of the ops of every module ``jit_<module>(<id>)``
        by the scope that claims them (``None``: no scope)."""
        rx = re.compile(rf"^jit_{re.escape(module)}\(\d+\)$")
        out = defaultdict(float)
        for key, secs in self.op_s.items():
            if rx.match(key[0]):
                out[scope_of(self.op_path.get(key))] += secs
        return dict(out)

    def top_unclaimed(self, module: str, k: int = 10) -> list:
        """The ``k`` instructions of ``module`` with the most device time
        that no scope claims."""
        rx = re.compile(rf"^jit_{re.escape(module)}\(\d+\)$")
        rows = defaultdict(float)
        for key, secs in self.op_s.items():
            if rx.match(key[0]) and scope_of(self.op_path.get(key)) is None:
                rows[key[1]] += secs
        return sorted(rows.items(), key=lambda kv: -kv[1])[:k]


def _sweep(spans, intervals):
    """Split ``intervals`` at every span boundary: yields (seconds, the
    spans open) for each piece."""
    points = []
    for s in spans:
        points += [(s.start, 1, s), (s.end, 0, s)]
    for a, b in intervals:
        points += [(a, 3, None), (b, 2, None)]
    points.sort(key=lambda p: (p[0], p[1]))
    open_, inside, prev = [], 0, None
    for t, kind, s in points:
        if inside and prev is not None and t > prev:
            yield (t - prev) * 1e-9, open_
        if kind == 1:
            open_ = open_ + [s]
        elif kind == 0:
            open_ = [o for o in open_ if o is not s]
        else:
            inside += 1 if kind == 3 else -1
        prev = t


# --------------------------------------------------------- the wire format
def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b, i, end):
    """(field number, value) of a message in ``b[i:end]``; a
    length-delimited value is its (start, end) in ``b``."""
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def op_paths(path: str) -> dict:
    """(program id, instruction) -> ``op_name`` path, for every op of a
    TPU device plane whose event metadata carries ``tf_op``. XSpace holds
    planes (1); a plane its name (2), event metadata (4) and stat metadata
    (5), both maps from id (1) to message (2); event metadata its name
    (2) and stats (5); a stat its metadata id (1) and a string (5) or a
    reference to a stat metadata name (7)."""
    with open(path, "rb") as f:
        b = memoryview(f.read())
    out = {}
    for num, plane in _fields(b, 0, len(b)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(b, *plane):
            if pnum == 2:
                name = _text(b, v)
            elif pnum == 4:
                events.append(v)
            elif pnum == 5:
                meta = dict(_fields(b, *dict(_fields(b, *v))[2]))
                stat_names[meta.get(1, 0)] = _text(b, meta[2]) \
                    if 2 in meta else ""
        if not name.startswith("/device:TPU:"):
            continue
        for entry in events:
            meta = dict(_fields(b, *entry)).get(2)
            if meta is None:
                continue
            instr, stats = None, {}
            for enum, v in _fields(b, *meta):
                if enum == 2:
                    m = _INSTRUCTION.match(_text(b, v))
                    instr = m.group(1) if m else None
                elif enum == 5:
                    st = dict(_fields(b, *v))
                    key = stat_names.get(st.get(1))
                    if key == "tf_op":
                        stats[key] = (_text(b, st[5]) if 5 in st
                                      else stat_names.get(st.get(7), ""))
                    elif key == "program_id":
                        stats[key] = st.get(3, st.get(4))
            if instr and "tf_op" in stats and "program_id" in stats:
                op = stats["tf_op"].rpartition(":")[0] or stats["tf_op"]
                out[(stats["program_id"], instr)] = op
    return out


# --------------------------------------------------------------- reduction
def host_spans(pd) -> list:
    """The program's spans on the host plane of a ``ProfileData``, by
    start."""
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append(Span(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    return sorted(spans, key=lambda s: s.start)


def reduce(path: str, window_span: str | None = None) -> ProgramTrace:
    """Reduce one ``.xplane.pb`` as :func:`xplane.reduce_trace` does, to
    the program's spans, the idle intervals and the ops by module."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = xplane._host_events(pd)
    shift = xplane._clock_shift(pd, host)
    spans = host_spans(pd)
    planes = xplane._device_planes(pd)
    if not planes:
        raise RuntimeError(f"{path}: no TPU device plane with XLA Ops")
    per_dev = []
    for plane in planes:
        ops, mods = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for e in line.events:
                s = e.start_ns + shift
                (ops if line.name == "XLA Ops" else mods).append(
                    (s, s + e.duration_ns, e.name))
        per_dev.append((ops, sorted(mods)))
    if window_span is not None:
        found = [(s, e) for s, e, nm in host if nm == window_span]
        if not found:
            raise RuntimeError(f"{path}: no host span {window_span!r}")
        window = found[0]
    else:
        every = [o for ops, _ in per_dev for o in ops]
        window = (min(o[0] for o in every), max(o[1] for o in every))
    w0, w1 = window
    paths = op_paths(path)
    out = ProgramTrace(window=window, devices=len(planes), spans=spans)
    for ops, mods in per_dev:
        starts = [m[0] for m in mods]
        clipped = [(max(s, w0), min(e, w1), nm) for s, e, nm in ops
                   if e > w0 and s < w1]
        for s, e, nm in clipped:
            op, _ = xplane.parse_op(nm)
            if op in xplane._CONTAINER:
                continue
            k = bisect.bisect_right(starts, s) - 1
            module = mods[k][2] if k >= 0 and mods[k][1] >= s else ""
            m = _INSTRUCTION.match(nm)
            key = (module, m.group(1) if m else nm.split(" ")[0])
            out.op_s[key] = out.op_s.get(key, 0.0) + (e - s) * 1e-9 \
                / len(planes)
            pid = _PROGRAM_ID.search(module)
            if pid and (int(pid.group(1)), key[1]) in paths:
                out.op_path[key] = paths[(int(pid.group(1)), key[1])]
        busy = xplane._merge([(s, e) for s, e, _ in clipped])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        out.idle.append([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                         if b > a])
    return out


# ---------------------------------------------------------------- measures
def decode_gap_p95_ms(t: ProgramTrace):
    """95th percentile, over consecutive decode steps that both had lanes
    and no wait for an arrival between them, of the next step's start
    less the previous step's end: the host time between two tokens beyond
    the step."""
    if t is None or N is None:
        return None
    steps = [s for s in t.named(N.DECODE) if s.attrs.get("lanes", 0) > 0]
    waits = [(w.start, w.end) for w in t.named(N.WAIT_FOR_ARRIVAL)]
    gaps = []
    for a, b in zip(steps, steps[1:]):
        if not any(ws < b.start and we > a.end for ws, we in waits):
            gaps.append((b.start - a.end) * 1e-6)
    return percentile(gaps, 95)


def lanes_per_step(t: ProgramTrace):
    """Mean active lanes over the window's decode steps."""
    if t is None or N is None:
        return None
    lanes = [s.attrs.get("lanes", 0) for s in t.named(N.DECODE)]
    return sum(lanes) / len(lanes) if lanes else None


def idle_engine_share(t: ProgramTrace):
    """Percent of the window in which the device is idle while an engine
    span is open and no wait for an arrival is (so the step around a wait
    does not count it), mean over devices."""
    if t is None or N is None:
        return None
    spans = [s for s in t.spans if s.name.startswith("engine.")]
    if not spans:
        return None
    secs = 0.0
    for ivs in t.idle:
        for piece, open_ in _sweep(spans, ivs):
            if open_ and all(s.name != N.WAIT_FOR_ARRIVAL for s in open_):
                secs += piece / t.devices
    return 100.0 * secs / t.window_s


def scope_share(t: ProgramTrace, module: str, scopes, claimed=None):
    """Percent of ``module``'s device time under ``scopes`` (with
    ``claimed`` given: under none of ``claimed``); None where no op of
    the module carries a scope, as in a program without them."""
    if t is None:
        return None
    by_scope = t.module_s(module)
    total = sum(by_scope.values())
    if total <= 0 or not any(k is not None for k in by_scope):
        return None
    if claimed is not None:
        part = sum(v for k, v in by_scope.items() if k not in claimed)
    else:
        part = sum(v for k, v in by_scope.items() if k in scopes)
    return 100.0 * part / total


def decode_scan_copy_share(t: ProgramTrace):
    """Percent of the decode program's device time in ops under none of
    the model's and sampler's scopes."""
    return scope_share(t, "pool_step", (), claimed=DECODE_WORK)


def optimizer_share(t: ProgramTrace):
    """Percent of the train step's device time under ``optimizer``."""
    return scope_share(t, "train_step", ("optimizer",))


def head_loss_share(t: ProgramTrace):
    """Percent of the train step's device time under ``lm_head`` or
    ``loss``, forward and backward."""
    return scope_share(t, "train_step", ("lm_head", "loss"))
