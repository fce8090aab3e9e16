"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* the window: the host event ``bench.window`` that the harness puts
  around the measured seconds (the whole trace where it is missing);
* device busy time: the union of the intervals in which an operation ran
  on each chip's op line, clipped to the window;
* kernel time: the summed self time of the device ops whose name, or
  whose ``long_name`` / ``tf_op`` stat, contains one of a kernel's name
  patterns;
* collective time: the same, for the collective ops;
* ``breakdown``: the device ops that took most self time on the first
  chip, and its longest idle gaps, each labelled by the host event that
  covered most of the gap.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_EVENT = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum",
                        re.IGNORECASE)
# host events that say nothing about what the host was doing
HOST_NOISE = re.compile(r"^(ThreadpoolListener|SlinkyThreadPool|"
                        r"\$profiler|bench\.window)")
TOP = 10

Interval = Tuple[int, int]


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def _self_times(events) -> List[Tuple[object, int]]:
    """(event, self time) on one line: an event's duration less the part
    its nested events on the same line cover."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    out = []
    stack: List[list] = []          # [event, end, child time]
    for e in evs:
        while stack and e.start_ns >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[0].duration_ns - done[2]))
        if stack:
            stack[-1][2] += min(e.end_ns, stack[-1][1]) - e.start_ns
        stack.append([e, e.end_ns, 0])
    while stack:
        done = stack.pop()
        out.append((done[0], done[0].duration_ns - done[2]))
    return out


def _stats(e) -> Dict[str, str]:
    try:
        return {k: str(v) for k, v in dict(e.stats).items()}
    except Exception:               # stats of a malformed event
        return {}


def _matches(e, patterns: Sequence[str]) -> bool:
    st = _stats(e)
    text = " ".join([e.name, st.get("long_name", ""), st.get("tf_op", ""),
                     st.get("hlo_op", "")])
    return any(p in text for p in patterns)


def short_name(name: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``: an op's HLO
    name without its shapes and operands."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_ops(plane) -> list:
    for ln in plane.lines:
        if ln.name in OP_LINES:
            return list(ln.events)
    return []


def reduce(pd, n_devices: int, kernels: Dict[str, Sequence[str]]) -> dict:
    """The numbers of one traced window of ``pd`` (a ``ProfileData``)."""
    planes = list(pd.planes)
    hosts = [p for p in planes if p.name.startswith("/host:")]
    devs = {}
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if m and int(m.group(1)) < n_devices:
            devs[int(m.group(1))] = p
    window = None
    host_events = []
    for p in hosts:
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW_EVENT:
                    window = (e.start_ns, e.end_ns)
                elif e.duration_ns > 0 and not HOST_NOISE.match(e.name):
                    host_events.append(e)
    # a chip on which nothing ran in the trace has no plane: all idle
    ops = {i: device_ops(devs[i]) if i in devs else []
           for i in range(n_devices)}
    if window is None:
        all_ops = [e for evs in ops.values() for e in evs]
        window = (min(e.start_ns for e in all_ops),
                  max(e.end_ns for e in all_ops))
    win_s = (window[1] - window[0]) / 1e9

    busy = {}
    kernel_s = {k: 0.0 for k in kernels}
    coll_s = {}
    top: Dict[str, float] = {}
    for i, evs in ops.items():
        clipped = [c for c in (_clip(e.start_ns, e.end_ns, window)
                               for e in evs) if c]
        union = _union(clipped)
        busy[i] = sum(e - s for s, e in union) / 1e9
        coll = 0.0
        for e, self_ns in _self_times(evs):
            if _clip(e.start_ns, e.end_ns, window) is None:
                continue
            frac = (min(e.end_ns, window[1]) - max(e.start_ns, window[0])) \
                / max(e.duration_ns, 1)
            t = self_ns * frac / 1e9
            for k, pats in kernels.items():
                if _matches(e, pats):
                    kernel_s[k] += t
            if COLLECTIVE.search(e.name):
                coll += t
            if i == min(ops):
                key = short_name(e.name)
                top[key] = top.get(key, 0.0) + t
        coll_s[i] = coll
        if i == min(ops):
            gaps = _gaps(union, window)
    first = min(ops)
    idle = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    labelled = [[_label(g, host_events), (g[1] - g[0]) / 1e9] for g in idle]
    return {
        "window_s": win_s,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_per_chip": [busy[i] for i in sorted(busy)],
        "kernels": {k: {"seconds": v} for k, v in kernel_s.items()},
        "collective_s_chip0": coll_s[first],
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                top.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": labelled,
        },
    }


def _gaps(union: List[Interval], window: Interval) -> List[Interval]:
    gaps, cur = [], window[0]
    for s, e in union:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    return gaps


def _label(gap: Interval, host_events) -> str:
    """The host event that overlaps most of ``gap`` (the innermost of
    equal overlaps: the shorter event wins a tie)."""
    best, best_ov = "host idle", 0
    for e in host_events:
        ov = min(e.end_ns, gap[1]) - max(e.start_ns, gap[0])
        if ov > best_ov or (ov == best_ov and ov > 0
                            and e.duration_ns < best[1]):
            best, best_ov = (e.name, e.duration_ns), ov
    return best[0] if isinstance(best, tuple) else best


def reduce_dir(trace_dir: Path, n_devices: int,
               kernels: Optional[Dict[str, Sequence[str]]] = None) -> dict:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    if kernels is None:
        kernels = default_kernels()
    return reduce(ProfileData.from_file(str(paths[-1])), n_devices, kernels)


def default_kernels() -> Dict[str, Sequence[str]]:
    """Each ``bench/roofline/<kernel>.py``'s ``NAME_PATTERNS``."""
    import importlib.util

    out = {}
    for p in sorted((Path(__file__).resolve().parent / "roofline")
                    .glob("*.py")):
        spec = importlib.util.spec_from_file_location(f"roof_{p.stem}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[p.stem] = tuple(mod.NAME_PATTERNS)
    return out
