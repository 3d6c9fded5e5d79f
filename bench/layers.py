"""Per-layer readings of a traced window, from the program's own scopes,
spans and counters.

``bench.trace.Summary`` holds device time per module and per op name, and
idle gaps named by the host's Python frames.  This module adds, from the
same ``.xplane.pb``:

* ``module_ops``: self seconds per (module base name, op name).  Each op
  belongs to the module whose interval on its chip contains it; the
  Summary's ``ops`` merges same-named ops of different modules (``fusion.N``
  of ``jit__prefill`` and of ``jit__decode``).
* ``spans``: per ``repro.`` host span name, its count, its seconds and the
  sum of each numeric counter it carries (``repro.obs`` in the program).
* ``span_idle``: the seconds of each device idle gap, by the innermost
  ``repro.`` span over its middle (``no host span`` outside them all).

The readings below turn them, with a scope map of ``bench.scopes``, into
the numbers ``PERF.md`` §3 lists: device time per layer per call, idle per
tick by host phase, and the share of prefill positions that hold a prompt.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

from bench import scopes
from bench.trace import MIN_GAP_NS, Span, base_name, busy_segments, name_gap, op_name, self_times

PREFIX = "repro."
STEP, ADMIT, PREFILL, DECODE, DRAIN, RETIRE = (
    "repro.serve." + p for p in ("step", "admit", "prefill", "decode", "drain", "retire")
)
TRAIN_MODULE, DECODE_MODULE = "jit_train_step", "jit__decode"


@dataclasses.dataclass
class Layers:
    module_ops: Dict[Tuple[str, str], float]  # (module, op) -> self seconds
    spans: Dict[str, dict]                    # name -> count, seconds, counters
    span_idle: Dict[str, float]               # span name -> idle seconds


def read_spans(path: str) -> List[tuple]:
    """(name, start ns, end ns, {counter: value}) of every ``repro.`` span."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    end = ev.start_ns + ev.duration_ns
                    out.append((ev.name, ev.start_ns, end, dict(ev.stats)))
    return out


def module_ops(ops: List[List[Span]], modules: List[List[Span]]) -> Dict[Tuple[str, str], float]:
    """Self seconds per (module, op), averaged over the chips; an op outside
    every module goes under the module ``""``."""
    out: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for o, m in zip(ops, modules):
        mods = sorted(m, key=lambda x: x[1])
        starts = [s for _, s, _ in mods]
        groups: Dict[str, List[Span]] = collections.defaultdict(list)
        for text, s, e in o:
            i = bisect.bisect_right(starts, s) - 1
            mod = base_name(mods[i][0]) if i >= 0 and s < mods[i][2] else ""
            groups[mod].append((op_name(text), s, e))
        for mod, spans in groups.items():
            for op, sec in self_times(spans).items():
                out[(mod, op)] += sec / len(ops)
    return dict(out)


def span_totals(spans: List[tuple]) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for name, s, e, stats in spans:
        t = out.setdefault(name, {"count": 0, "seconds": 0.0, "counters": {}})
        t["count"] += 1
        t["seconds"] += (e - s) / 1e9
        for k, v in stats.items():
            if isinstance(v, (int, float)):
                t["counters"][k] = t["counters"].get(k, 0) + v
    return out


def span_idle(ops: List[List[Span]], spans: List[tuple]) -> Dict[str, float]:
    """Idle seconds between the first chip's busy segments (gaps of
    ``MIN_GAP_NS`` or more, as ``bench.trace`` counts them), per span."""
    host = [(n, s, e) for n, s, e, _ in spans]
    out: Dict[str, float] = collections.defaultdict(float)
    segs = busy_segments(ops[0])
    for (_, a), (b, _) in zip(segs, segs[1:]):
        if b - a >= MIN_GAP_NS:
            out[name_gap(host, (a + b) / 2)] += (b - a) / 1e9
    return dict(out)


def reduce(ops, modules, spans) -> Layers:
    return Layers(module_ops(ops, modules), span_totals(spans), span_idle(ops, spans))


def load(path: str) -> Layers:
    from bench.trace import read_planes

    ops, modules, _ = read_planes(path)
    return reduce(ops, modules, read_spans(path))


# ---------------------------------------------------------------- readings

def scope_seconds(layers: Layers, module: str, op_map: Dict[str, str]) -> Dict[Optional[str], float]:
    """Self seconds of ``module``'s ops by ``bench.scopes.classify`` of their
    op_name (None: no scope); ops the map does not hold go under ``"?"``."""
    out: Dict[Optional[str], float] = collections.defaultdict(float)
    for (mod, op), sec in layers.module_ops.items():
        if mod == module:
            out[scopes.classify(op_map[op]) if op in op_map else "?"] += sec
    return dict(out)


def scope_ms(layers: Layers, scope_map: dict, module: str, scope: str, calls: float) -> Optional[float]:
    """Device ms per call of ``module`` in ops of ``scope`` (0.0 if none);
    None without the module's map or a call."""
    if module not in scope_map or not calls:
        return None
    return 1e3 * scope_seconds(layers, module, scope_map[module]).get(scope, 0.0) / calls


def idle_ms(layers: Layers, names, ticks: float) -> Optional[float]:
    """Idle ms per tick under the spans ``names``; None without a tick."""
    if not ticks:
        return None
    return 1e3 * sum(layers.span_idle.get(n, 0.0) for n in names) / ticks


def prefill_useful(layers: Layers) -> Optional[float]:
    """100 x prompt tokens over positions the window's prefills computed."""
    c = layers.spans.get(PREFILL, {}).get("counters", {})
    if not c.get("positions"):
        return None
    return 100.0 * c.get("prompt_tokens", 0) / c["positions"]


def readings(layers: Layers, scope_map: dict, modules: Dict[str, List[float]]) -> Dict[str, Optional[float]]:
    """The per-layer numbers of a train or serve window."""
    steps = modules.get(TRAIN_MODULE, [0, 0.0])[0]
    chunks = modules.get(DECODE_MODULE, [0, 0.0])[0]
    ticks = layers.spans.get(STEP, {}).get("count", 0)
    out = {}
    if steps:
        for s in ("fwd", "bwd", "optimizer"):
            out[f"{s}_ms.train"] = scope_ms(layers, scope_map, TRAIN_MODULE, s, steps)
    if chunks:
        for s in ("materialize", "sample"):
            out[f"{s}_ms.serve"] = scope_ms(layers, scope_map, DECODE_MODULE, s, chunks)
        out["drain_idle_ms.serve"] = idle_ms(layers, (DRAIN, RETIRE), ticks)
        out["dispatch_idle_ms.serve"] = idle_ms(layers, (ADMIT, PREFILL, DECODE), ticks)
        out["prefill_useful.serve"] = prefill_useful(layers)
    return out
