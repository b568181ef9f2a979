"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What is read, per device plane ``/device:TPU:<n>``:

* the ``XLA Ops`` line: one event per operation the device ran, named by
  its HLO instruction (``%name = shape op(...)``). Busy time is the union
  of these intervals. Ops that only hold others (``while``, ``call``,
  ``conditional``) are left out of the per-op times, since their
  children are on the line too. A kernel is a ``tpu_custom_call``, keyed
  ``<instruction name>#<operand count>``: inside a jitted step the
  forward and backward Pallas kernels of one function can carry the same
  instruction name, and their operands tell them apart.
* collectives: ops whose HLO op is an all-reduce, all-gather,
  reduce-scatter, all-to-all or collective-permute (their ``-start``/
  ``-done`` halves included). The exposed part is the time in which such
  an op runs and no other op does, on that device.

The host plane ``/host:CPU`` gives the spans of the host: the
benchmark's own ``TraceAnnotation`` spans and JAX's dispatch events. Each
idle gap on the device is named after the host event that covers most of
it. The device clock is shifted onto the host's by the smallest lag seen
between a jitted call's dispatch on the host and the start of its module
on the device.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?$")
_NAME = re.compile(r"^%?([^ =]+?)(\.\d+)?\s*=")
# ops that only hold other ops: their time is their children's
_CONTAINER = {"while", "conditional", "call"}
_MODULE = re.compile(r"^jit_(.*)\(\d+\)$")
_DISPATCH = re.compile(r"^PjitFunction\((.*)\)$")
# host events too generic to say what the host was doing
_GENERIC = re.compile(r"^(\$|ThreadpoolListener|futex|Acquire semaphore|"
                      r"Release semaphore|MemoryAllocation|"
                      r"MemoryDeallocation)")


def op_name(event_name: str) -> str:
    """``%jvp_jit__flash_attention__.1 = (...)`` -> the instruction name
    without the leading ``%`` and its numeric suffix."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


def parse_op(event_name: str) -> tuple:
    """``%x = <shape> <op>(<operands>), ...`` -> (op, operand count). The
    shape may be a tuple of any length, so it is skipped by matching its
    parentheses, not by a bounded search."""
    rest = event_name.split(" = ", 1)[-1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[-1]
    op, _, args = rest.strip().partition("(")
    depth, n = 1, 0
    for ch in args:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        elif ch == "%" and depth == 1:
            n += 1
    return op.strip(), n


def _union(intervals):
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Reduced:
    """A trace reduced to seconds, averaged over the devices traced."""
    window_s: float
    busy_s: float                       # union of op intervals, mean
    devices: int
    op_s: dict = field(default_factory=dict)        # name -> seconds, mean
    custom_call_s: dict = field(default_factory=dict)
    custom_call_n: dict = field(default_factory=dict)  # calls, mean
    collective_s: float = 0.0
    collective_exposed_s: float = 0.0
    idle_gaps: list = field(default_factory=list)   # [(name, seconds)]

    def kernel_s(self, pattern: str) -> float:
        """Summed device seconds of the custom calls (Pallas kernels)
        whose key ``<instruction name>#<operand count>`` matches
        ``pattern``, mean over devices."""
        rx = re.compile(pattern)
        return sum(t for n, t in self.custom_call_s.items() if rx.search(n))

    def kernel_calls(self, pattern: str) -> float:
        """Calls of the matching custom calls, mean over devices."""
        rx = re.compile(pattern)
        return sum(c for n, c in self.custom_call_n.items() if rx.search(n))

    def idle_share(self):
        """Percent of the window in which no op ran on the device, mean over
        devices; None for an empty window."""
        if self.window_s <= 0:
            return None
        return 100.0 * (self.window_s - self.busy_s) / self.window_s

    def top_ops(self, k: int = 10):
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            and any(ln.name == "XLA Ops" for ln in p.lines)]


def _host_events(pd):
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return out


def _clock_shift(pd, host) -> float:
    """Nanoseconds to add to device times to put them on the host clock:
    the smallest lag between a dispatch and the module's start, taken as
    zero (a module cannot start before it was dispatched)."""
    dispatch: dict = {}
    for s, _, name in host:
        m = _DISPATCH.match(name)
        if m:
            dispatch.setdefault(m.group(1), []).append(s)
    lags = []
    for plane in _device_planes(pd):
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                m = _MODULE.match(e.name)
                starts = dispatch.get(m.group(1), []) if m else []
                before = [e.start_ns - s for s in starts]
                if before:
                    lags.append(min(before, key=abs))
    return -min(lags) if lags else 0.0


def _what_host_did(host, a, b, exclude) -> str:
    """The innermost host event that covers at least half of the gap
    [a, b); failing that, the one that covers most of it."""
    half, inner, most = (b - a) / 2, None, (0.0, None)
    for s, e, name in host:
        if name == exclude or _GENERIC.match(name):
            continue
        c = min(b, e) - max(a, s)
        if c >= half and (inner is None or e - s < inner[0]):
            inner = (e - s, name)
        if c > most[0]:
            most = (c, name)
    if inner is not None:
        return inner[1]
    return most[1] or "no host span"


def reduce_trace(path: str, window_span: str | None = None,
                 gaps: int = 10) -> Reduced:
    """Reduce one ``.xplane.pb``. The window is the first host span named
    ``window_span``, and clips every interval; without one it runs from
    the first to the last device op."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = _host_events(pd)
    shift = _clock_shift(pd, host)
    window_ns = None
    if window_span is not None:
        spans = [(s, e) for s, e, nm in host if nm == window_span]
        if not spans:
            raise RuntimeError(f"{path}: no host span {window_span!r}")
        window_ns = spans[0]
    planes = _device_planes(pd)
    if not planes:
        raise RuntimeError(f"{path}: no TPU device plane with XLA Ops")
    per_dev = []
    for plane in planes:
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s = e.start_ns + shift
                ops.append((s, s + e.duration_ns, e.name))
        per_dev.append(ops)
    if window_ns is None:
        every = [o for ops in per_dev for o in ops]
        window_ns = (min(o[0] for o in every), max(o[1] for o in every))
    w0, w1 = window_ns
    red = Reduced(window_s=(w1 - w0) * 1e-9, busy_s=0.0, devices=len(planes))
    idle_all = []
    n = len(planes)
    for ops in per_dev:
        clipped = [(max(s, w0), min(e, w1), nm) for s, e, nm in ops
                   if e > w0 and s < w1]
        spans = [(s, e) for s, e, _ in clipped]
        red.busy_s += _union(spans) * 1e-9 / n
        coll, other = [], []
        for s, e, nm in clipped:
            op, operands = parse_op(nm)
            if op in _CONTAINER:
                continue
            name = op_name(nm)
            red.op_s[name] = red.op_s.get(name, 0.0) + (e - s) * 1e-9 / n
            if "custom_call_target=\"tpu_custom_call\"" in nm:
                key = f"{name}#{operands}"
                red.custom_call_s[key] = (red.custom_call_s.get(key, 0.0)
                                          + (e - s) * 1e-9 / n)
                red.custom_call_n[key] = (red.custom_call_n.get(key, 0.0)
                                          + 1.0 / n)
            (coll if COLLECTIVE.match(op) else other).append((s, e))
        red.collective_s += _union(coll) * 1e-9 / n
        red.collective_exposed_s += (
            _union(coll + other) - _union(other)) * 1e-9 / n
        busy = _merge(spans)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle_all.append((a, b))
    idle_all.sort(key=lambda iv: iv[0] - iv[1])
    named = []
    for a, b in idle_all[:gaps]:
        named.append((_what_host_did(host, a, b, window_span),
                      (b - a) * 1e-9))
    red.idle_gaps = named
    return red


def find_xplane(log_dir: str) -> str:
    found = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]
