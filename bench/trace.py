"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

Per TPU plane: every op of the ``XLA Ops`` line, every jitted module of the
``XLA Modules`` line; from the host plane, the spans of Python calls and
``TraceAnnotation``s.  From them:

* ``busy_s``: the union of the op intervals, averaged over the chips;
* ``modules``: device time per jitted module (``jit_train_step``, ...);
* ``custom_calls``: device time per custom call (a Pallas kernel is one,
  named after its kernel function, e.g. ``fused_adamw4``);
* ``ops``: self time per op (nested ops, such as a loop's body, are taken
  out of the op that holds them);
* ``gaps``: the device's idle gaps of a microsecond or more, each named by the innermost host span
  over its middle, i.e. by what the host was doing.

The window is the traced loop's own length on the host clock.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Tuple

import jax

Span = Tuple[str, float, float]  # (name, start ns, end ns)
MIN_GAP_NS = 1000  # shorter pauses between back-to-back ops are not idle gaps

_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """``%fused_adamw4.4 = (...) custom-call(...)`` -> ``fused_adamw4.4``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def base_name(name: str) -> str:
    """Instruction or module name without its ``.N`` / ``(hash)`` suffix."""
    return _SUFFIX.sub("", name.split("(", 1)[0])


def is_custom_call(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def union_s(spans: List[Span]) -> float:
    total, end = 0.0, float("-inf")
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total / 1e9


def busy_segments(spans: List[Span]) -> List[Tuple[float, float]]:
    segs: List[List[float]] = []
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        if segs and s <= segs[-1][1]:
            segs[-1][1] = max(segs[-1][1], e)
        else:
            segs.append([s, e])
    return [(a, b) for a, b in segs]


def self_times(spans: List[Span]) -> Dict[str, float]:
    """Seconds per op name, each op less the ops nested in it."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []  # [name, end, child ns]

    def close(item):
        out[item[0]] += (item[3] - item[2]) / 1e9

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    while stack:
        close(stack.pop())
    return dict(out)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: Dict[str, List[float]]       # base name -> [count, seconds]
    custom_calls: Dict[str, List[float]]  # base name -> [count, seconds]
    ops: Dict[str, float]                 # op name -> self seconds
    gaps: List[Tuple[str, float]]         # (host span, seconds), longest first

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps: Dict[str, float] = collections.defaultdict(float)
        for name, sec in self.gaps:
            gaps[name] += sec
        top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in top_gaps]}


def read_planes(path: str):
    """(per-chip op spans, per-chip module spans, host spans) of a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            o, m = [], []
            for line in plane.lines:
                dst = {"XLA Ops": o, "XLA Modules": m}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
            ops.append(o)
            modules.append(m)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("$", "bench.")):
                        host.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, modules, host


def name_gap(host: List[Span], mid: float) -> str:
    best, best_len = "no host span", float("inf")
    for name, s, e in host:
        if s <= mid <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def reduce(ops: List[List[Span]], modules: List[List[Span]], host: List[Span],
           window_s: float) -> Summary:
    if not ops or not any(ops):
        raise ValueError("the trace holds no device op")
    busy = sum(union_s(o) for o in ops) / len(ops)
    mods: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for m in modules:
        for name, s, e in m:
            mods[base_name(name)][0] += 1
            mods[base_name(name)][1] += (e - s) / 1e9
    calls: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    named = []
    for o in ops:
        for text, s, e in o:
            name = op_name(text)
            named.append((name, s, e))
            if is_custom_call(text):
                calls[base_name(name)][0] += 1
                calls[base_name(name)][1] += (e - s) / 1e9
    gaps = []
    segs = busy_segments(ops[0])
    for (_, a), (b, _) in zip(segs, segs[1:]):
        if b - a >= MIN_GAP_NS:
            gaps.append((name_gap(host, (a + b) / 2), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    n = len(ops)
    return Summary(
        window_s=window_s,
        busy_s=busy,
        modules={k: [v[0] / n, v[1] / n] for k, v in mods.items()},
        custom_calls={k: [v[0] / n, v[1] / n] for k, v in calls.items()},
        ops={k: v / n for k, v in self_times(named).items()},
        gaps=gaps,
    )


def load(directory: str, window_s: float) -> Summary:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"want one .xplane.pb under {directory}, found {len(files)}")
    return reduce(*read_planes(files[0]), window_s)


class Recorder(contextlib.AbstractContextManager):
    """Traces the ``with`` body into ``directory`` and reduces it on exit;
    the directory is deleted afterwards."""

    def __init__(self, directory: str):
        self.directory = directory
        self._summary = None

    def __enter__(self):
        jax.profiler.start_trace(self.directory)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        window = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                self._summary = load(self.directory, window)
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
        return False

    def summary(self) -> Summary:
        return self._summary
