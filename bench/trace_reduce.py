"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The trace holds, per TPU chip, a plane ``/device:TPU:<n>`` whose line
``XLA Modules`` has one event per program execution (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` has one event
per HLO operation. The host plane ``/host:CPU`` holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans. All of them share one clock.

- busy: the union of the ``XLA Ops`` intervals inside the traced window,
  averaged over the chips; idle share is 1 - busy / window;
- programs: device seconds and executions per program, by function name;
- top ops: device seconds per operation, named ``<program>/<op>``;
- gaps: the idle intervals of chip 0 inside the window, longest first, each
  labelled by the caller (what the host was doing then).
"""
from __future__ import annotations

import bisect
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def _program_name(event_name: str) -> str:
    """``jit_layer_apply(1607850917329800840)`` -> ``jit_layer_apply``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _op_name(event_name: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``fusion.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Interval, w: Interval) -> Optional[Interval]:
    a, b = max(iv[0], w[0]), min(iv[1], w[1])
    return (a, b) if b > a else None


def _events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def host_spans(pd, name: str) -> List[Interval]:
    """Intervals of every host event called ``name`` (a TraceAnnotation)."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name == name)
    return sorted(out)


def reduce_profile(pd, window: Optional[Interval] = None,
                   label: Optional[Callable[[Interval], str]] = None,
                   n_top: int = 10) -> Optional[Dict]:
    """Device numbers of a loaded ``jax.profiler.ProfileData`` within
    ``window`` (ns on the trace's clock; default: from the first to the last
    device operation). Returns None when the trace holds no TPU plane."""
    chips = sorted((p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)),
                   key=lambda p: p.name)
    if not chips:
        return None
    ops = {p.name: _events(p, "XLA Ops") for p in chips}
    mods = {p.name: _events(p, "XLA Modules") for p in chips}
    if window is None:
        every = [iv for evs in ops.values() for _, *iv in evs]
        if not every:
            return None
        window = (min(a for a, _ in every), max(b for _, b in every))
    busy_ns = []
    programs: Dict[str, List[float]] = {}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for i, chip in enumerate(chips):
        merged = merge([c for _, *iv in ops[chip.name]
                        if (c := _clip(tuple(iv), window))])
        busy_ns.append(sum(b - a for a, b in merged))
        spans = sorted((a, b, _program_name(n)) for n, a, b in mods[chip.name])
        starts = [s for s, _, _ in spans]
        for a, b, name in spans:
            if _clip((a, b), window):
                rec = programs.setdefault(name, [0.0, 0])
                rec[0] += (b - a) * 1e-9
                rec[1] += 1
        for name, a, b in ops[chip.name]:
            c = _clip((a, b), window)
            if not c:
                continue
            j = bisect.bisect_right(starts, a) - 1
            owner = spans[j][2] if j >= 0 and a < spans[j][1] else "none"
            key = f"{owner}/{_op_name(name)}"
            op_time[key] = op_time.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        if i == 0:
            edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((label((a, b)) if label else "idle",
                                 (b - a) * 1e-9, a))
    window_s = (window[1] - window[0]) * 1e-9
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return dict(
        chips=len(chips),
        window_s=window_s,
        busy_s=busy_s,
        idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
        programs={k: dict(seconds=v[0], count=v[1])
                  for k, v in sorted(programs.items())},
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:n_top],
        gaps=[[name, s] for name, s, _ in gaps[:n_top]],
        gap_total_s=sum(s for _, s, _ in gaps),
    )


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
