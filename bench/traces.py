"""Reduce a profiler trace to device busy time, program and kernel device
times, idle share and the longest idle gaps.

A trace is read once into plain event records (``load``), so that the
reduction runs the same on a chip run's ``.xplane.pb`` and on the small
recorded trace the tests keep.  An event is a dict with ``plane``,
``line``, ``name``, ``start_ns``, ``dur_ns`` and ``stats``.

* Device events are those on ``/device:TPU:<n>`` planes.  Busy time is
  the union of the intervals of the ``XLA Ops`` line (one event per
  operation that ran), clipped to the traced window.
* A program is found by its module name on the ``XLA Modules`` line
  (``jit_<function>(<id>)``): ``program_seconds`` sums the device time
  of the modules whose name holds a given substring.
* A kernel is found among the ``XLA Ops`` events by a substring of its
  name or of its ``long_name``/``tf_op`` statistics.
* An idle gap is a stretch of the window with no device operation on
  any chip.  It is named after the innermost host span of the benchmark
  (``bench.*``) that covers its middle, else after the shortest host
  event that does, else ``"no host span"``.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str) -> List[Dict]:
    """Events of a trace: an ``.xplane.pb`` file, a directory holding one
    (the profiler's ``plugins/profile/<time>/`` layout), or a JSON list
    of event records."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                stats = {}
                for k, v in e.stats:
                    stats[k] = v if isinstance(v, (int, float, str)) else str(v)
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start_ns": float(e.start_ns),
                            "dur_ns": float(e.duration_ns), "stats": stats})
    return out


def is_device(ev: Dict) -> bool:
    return ev["plane"].startswith("/device:TPU:")


def device_planes(events: Iterable[Dict]) -> List[str]:
    return sorted({e["plane"] for e in events if is_device(e)})


def union_seconds(intervals: Sequence[Tuple[float, float]],
                  lo: float, hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``, in the intervals' unit divided by 1e9."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _intervals(events: Iterable[Dict], line: str,
               plane: Optional[str] = None) -> List[Tuple[float, float]]:
    return [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
            if is_device(e) and e["line"] == line
            and (plane is None or e["plane"] == plane)]


def window_bounds(events: Sequence[Dict]) -> Tuple[float, float]:
    """The traced window: from the first to the last event of any plane."""
    starts = [e["start_ns"] for e in events]
    ends = [e["start_ns"] + e["dur_ns"] for e in events]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def busy_seconds(events: Sequence[Dict], lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(union_seconds(_intervals(events, OPS_LINE, p), lo, hi)
               for p in planes) / len(planes)


def program_seconds(events: Iterable[Dict], substring: str,
                    lo: float, hi: float) -> Tuple[float, int]:
    """(device seconds, executions) of the programs whose module name
    holds ``substring``, averaged over the chips."""
    evs = [e for e in events if is_device(e) and e["line"] == MODULES_LINE
           and substring in e["name"]
           and lo <= e["start_ns"] < hi]
    planes = {e["plane"] for e in evs} or {None}
    secs = sum(e["dur_ns"] for e in evs) / 1e9 / len(planes)
    return secs, len(evs) // len(planes)


def op_text(ev: Dict) -> str:
    st = ev.get("stats", {})
    return " ".join(str(st.get(k, "")) for k in ("long_name", "tf_op",
                                                  "hlo_op")) + " " + ev["name"]


def kernel_events(events: Iterable[Dict], substrings: Sequence[str],
                  lo: float, hi: float) -> List[Dict]:
    return [e for e in events if is_device(e) and e["line"] == OPS_LINE
            and lo <= e["start_ns"] < hi
            and any(s in op_text(e) for s in substrings)]


def op_name(ev: Dict) -> str:
    """An operation's name without the HLO text the TPU trace appends
    (``%while.12 = (s32[], ...) while(...)`` becomes ``%while.12``)."""
    return ev["name"].split(" = ", 1)[0]


def top_ops(events: Iterable[Dict], lo: float, hi: float,
            n: int = 10) -> List[List]:
    """The device operations that took the most time, [name, seconds],
    averaged over the chips.  An operation that holds others (a loop)
    counts its whole span."""
    tot: Dict[str, float] = {}
    planes = set()
    for e in events:
        if is_device(e) and e["line"] == OPS_LINE and lo <= e["start_ns"] < hi:
            k = op_name(e)
            tot[k] = tot.get(k, 0.0) + e["dur_ns"] / 1e9
            planes.add(e["plane"])
    k = max(len(planes), 1)
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Dict], lo: float, hi: float,
              n: int = 10) -> List[List]:
    """The longest stretches with no operation on any chip, each
    [what the host was doing, seconds]."""
    busy = sorted(_intervals(events, OPS_LINE))
    gaps: List[Tuple[float, float]] = []
    cur = lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    host = [e for e in events if not is_device(e) and e["dur_ns"] > 0]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [h for h in host
                    if h["start_ns"] <= mid < h["start_ns"] + h["dur_ns"]]
        mine = [h for h in covering if h["name"].startswith("bench.")]
        pick = min(mine or covering, key=lambda h: h["dur_ns"], default=None)
        out.append([pick["name"] if pick else "no host span",
                    (e - s) / 1e9])
    return out


def reduce(events: Sequence[Dict]) -> Dict:
    """The whole-window numbers every traced run reports."""
    lo, hi = window_bounds(events)
    window_s = (hi - lo) / 1e9
    busy = busy_seconds(events, lo, hi)
    return {"lo": lo, "hi": hi, "window_s": window_s, "busy_s": busy,
            "chips": len(device_planes(events)),
            "device_ops": top_ops(events, lo, hi),
            "idle_gaps": idle_gaps(events, lo, hi)}


def summary(events: Sequence[Dict], n: int = 40) -> Dict:
    """For reading a trace by hand: per (plane, line), the event count and
    the names that took the most time, each with one event's statistics."""
    groups: Dict[Tuple[str, str], Dict[str, List]] = {}
    for e in events:
        g = groups.setdefault((e["plane"], e["line"]), {})
        rec = g.setdefault(e["name"], [0.0, 0, e.get("stats", {})])
        rec[0] += e["dur_ns"] / 1e9
        rec[1] += 1
    out = {}
    for (plane, line), names in groups.items():
        top = sorted(names.items(), key=lambda kv: -kv[1][0])[:n]
        out[f"{plane} | {line}"] = {
            "events": sum(v[1] for v in names.values()),
            "top": [[k, v[0], v[1], v[2]] for k, v in top]}
    return out


def main(argv=None) -> int:
    """``python3 bench/traces.py <trace dir> [--excerpt OUT --ms N]``: print
    the per-line summary of a kept trace as JSON; with ``--excerpt``,
    also write every event of the N milliseconds in the middle of the
    traced window to OUT (the form the tests keep)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--excerpt", default=None)
    ap.add_argument("--ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    events = load(args.path)
    print(json.dumps(summary(events), indent=1, default=str))
    if args.excerpt:
        lo, hi = window_bounds(events)
        mid = (lo + hi) / 2
        a, b = mid - args.ms * 5e5, mid + args.ms * 5e5
        keep = [e for e in events
                if e["start_ns"] < b and e["start_ns"] + e["dur_ns"] > a]
        with open(args.excerpt, "w") as f:
            json.dump(keep, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
