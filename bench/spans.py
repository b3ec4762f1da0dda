"""Attribution of a profiler trace to the engine's host phases and to the
round program's stages.

The serving engine wraps its phases in profiler spans named
``engine.<phase>`` (``serving/engine.py``), and the round program runs
its six stages under ``jax.named_scope``s (``core/spec_decode.py``).
This module reads both out of a trace loaded by ``bench/traces.py``:

* ``idle_by_span``: every idle instant of the window (no operation on
  any chip) is charged to the innermost ``engine.*`` span that covers
  it, else to ``"no engine span"``.
* ``stage_seconds``: the device time of the round program's operations
  in each stage.  The TPU trace names an operation by its HLO
  instruction (``%while.12``) and carries no scope path, so the stage
  of an instruction is read from the ``op_name`` metadata of the
  program's compiled text (``stage_map``).
* ``phase_seconds``: the wall seconds of each engine phase, from its
  spans.

``attribution(run)`` gathers them for a traced run; the per-layer
readers in ``bench/metrics/`` take their numbers from it.  On a program
without the spans or the scopes every reading is empty, and the readers
report nothing.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import traces

ENGINE = "engine."
NO_SPAN = "no engine span"
STAGES = ("propose", "verify", "reject", "signal", "commit", "predict")
ROUND_PROGRAM = "spec_decode_round_impl"
PHASES = ("plan", "dispatch", "collect")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)')
_CALL_LISTS = re.compile(
    r'\b(?:branch_computations|called_computations)=\{([^}]*)\}')


def engine_spans(events: Iterable[Dict]) -> List[Dict]:
    return [e for e in events if not traces.is_device(e)
            and e["dur_ns"] > 0 and e["name"].startswith(ENGINE)]


# ---------------------------------------------------------------------------
# idle time, charged to the host spans
# ---------------------------------------------------------------------------

def idle_by_span(events: Sequence[Dict], lo: float,
                 hi: float) -> Dict[str, float]:
    """Seconds of the window ``[lo, hi)`` in which no operation ran on any
    chip, per innermost ``engine.*`` span covering them, and under
    ``"no engine span"`` where none does.  Every idle instant is charged,
    however short its gap; nested spans give their time to the inner
    one.  The values sum to the window's idle seconds."""
    busy = sorted(traces._intervals(events, traces.OPS_LINE))
    spans = engine_spans(events)
    cuts = {lo, hi}
    for h in spans:
        for t in (h["start_ns"], h["start_ns"] + h["dur_ns"]):
            if lo < t < hi:
                cuts.add(t)
    # idle stretches of the window: the gaps between the merged busy
    # intervals
    idle: List[Tuple[float, float]] = []
    cur = lo
    for s, e in busy:
        if s > cur:
            idle.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        idle.append((cur, hi))
    for s, e in idle:
        cuts.update((s, e))
    edges = sorted(c for c in cuts if lo <= c <= hi)
    out: Dict[str, float] = {}
    i = 0
    # spans by start, to find the innermost one covering a piece
    by_start = sorted(spans, key=lambda h: h["start_ns"])
    for a, b in zip(edges, edges[1:]):
        if b <= a:
            continue
        mid = (a + b) / 2
        while i < len(idle) and idle[i][1] <= mid:
            i += 1
        if i >= len(idle) or not idle[i][0] <= mid < idle[i][1]:
            continue
        pick = None
        for h in by_start:
            if h["start_ns"] > mid:
                break
            if mid < h["start_ns"] + h["dur_ns"] and (
                    pick is None or h["dur_ns"] < pick["dur_ns"]):
                pick = h
        name = pick["name"] if pick is not None else NO_SPAN
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


# ---------------------------------------------------------------------------
# the round program's stages
# ---------------------------------------------------------------------------

def stage_of(op_name: str) -> Optional[str]:
    """The first stage scope on an ``op_name`` path, if any."""
    for part in op_name.split("/"):
        if part in STAGES:
            return part
    return None


def out_shape(rhs: str) -> str:
    """The output shape at the head of an instruction's right-hand side
    (``f32[8]{0} add(...)`` gives ``f32[8]{0}``; a tuple shape is read to
    its closing parenthesis)."""
    if not rhs.startswith("("):
        return rhs.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rhs):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return rhs[:i + 1]
    return rhs


def stage_map(hlo_text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """Instruction name (without ``%``) to (stage, output shape), from a
    compiled program's text (``Compiled.as_text()``).  An instruction's
    stage is the scope on its own ``op_name``; one without (a copy the
    compiler inserted, an operation in a loop body) takes the stage of
    the instruction whose computation holds it."""
    comp = None
    owner: Dict[str, str] = {}            # instruction -> its computation
    own: Dict[str, Optional[str]] = {}    # instruction -> its own stage
    shape: Dict[str, str] = {}
    callers: Dict[str, List[str]] = {}    # computation -> calling instrs
    for line in hlo_text.splitlines():
        if not line or line.startswith(("HloModule", "}")):
            continue
        if not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.split("ENTRY ", 1)[-1].split(None, 1)[0]
                comp = head.lstrip("%")
            continue
        body = line.strip()
        if body.startswith("ROOT "):
            body = body[5:]
        if " = " not in body or comp is None:
            continue
        name, rhs = body.split(" = ", 1)
        name = name.strip().lstrip("%")
        owner[name] = comp
        shape[name] = out_shape(rhs)
        m = _OP_NAME.search(rhs)
        own[name] = stage_of(m.group(1)) if m else None
        called = _CALLS.findall(rhs)
        for group in _CALL_LISTS.findall(rhs):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        for c in called:
            callers.setdefault(c, []).append(name)

    memo: Dict[str, Optional[str]] = {}

    def comp_stage(c: str, seen: Tuple[str, ...]) -> Optional[str]:
        if c not in memo:
            found = {resolve(i, seen + (c,)) for i in callers.get(c, [])
                     if owner[i] not in seen + (c,)} - {None}
            memo[c] = found.pop() if len(found) == 1 else None
        return memo[c]

    def resolve(i: str, seen: Tuple[str, ...] = ()) -> Optional[str]:
        return own[i] or comp_stage(owner[i], seen)

    return {i: (resolve(i), shape[i]) for i in owner}


def _program_of(ops: List[Tuple[str, str]],
                programs: Sequence[Dict[str, Tuple[Optional[str], str]]]
                ) -> Dict[str, Tuple[Optional[str], str]]:
    """The program, among several compiled at different draft buckets,
    whose instructions match most of one execution's operations by name
    and output shape."""
    def score(p):
        return sum(p.get(n, (None, None))[1] == s for n, s in ops)
    return max(programs, key=score)


def stage_seconds(events: Sequence[Dict], lo: float, hi: float,
                  programs: Sequence[Dict[str, Tuple[Optional[str], str]]],
                  program: str = ROUND_PROGRAM) -> Dict[str, float]:
    """Device seconds of each stage of the round program in ``[lo, hi)``,
    averaged over the chips.  ``programs`` are ``stage_map``s of the
    program as compiled at each draft bucket; each execution (an event
    on the ``XLA Modules`` line) is read with the one its operations
    match.  A stage's time is the union of the intervals of the
    execution's operations in it (an operation holding others, a loop,
    covers them).  ``"program"`` is the program's own device time,
    ``"executions"`` its count, and ``"unscoped"`` the union of its
    operations that carry no stage."""
    out: Dict[str, float] = {"program": 0.0, "executions": 0.0}
    planes = traces.device_planes(events)
    if not programs:
        return out
    for plane in planes:
        runs = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                      for e in events if e["plane"] == plane
                      and e["line"] == traces.MODULES_LINE
                      and program in e["name"] and lo <= e["start_ns"] < hi)
        starts = [a for a, _ in runs]
        ops_of: List[List[Dict]] = [[] for _ in runs]
        for e in events:
            if e["plane"] == plane and e["line"] == traces.OPS_LINE:
                j = bisect.bisect_right(starts, e["start_ns"]) - 1
                if j >= 0 and e["start_ns"] < runs[j][1]:
                    ops_of[j].append(e)
        per: Dict[str, List[Tuple[float, float]]] = {}
        for (a, b), ops in zip(runs, ops_of):
            out["program"] += (b - a) / 1e9
            out["executions"] += 1
            named = [(traces.op_name(e).lstrip("%"),
                      out_shape(e["name"].split(" = ", 1)[-1])) for e in ops]
            prog = _program_of(named, programs)
            for e, (n, _) in zip(ops, named):
                stage = prog.get(n, (None, ""))[0] or "unscoped"
                per.setdefault(stage, []).append(
                    (e["start_ns"], e["start_ns"] + e["dur_ns"]))
        for stage, iv in per.items():
            out[stage] = out.get(stage, 0.0) + traces.union_seconds(
                iv, lo, hi)
    n = max(len(planes), 1)
    return {k: v / n for k, v in out.items()}


# ---------------------------------------------------------------------------
# host phases
# ---------------------------------------------------------------------------

def phase_seconds(events: Iterable[Dict], lo: float,
                  hi: float) -> Dict[str, float]:
    """Wall seconds of each ``engine.*`` span name in ``[lo, hi)``, summed
    over its spans (clipped to the window)."""
    out: Dict[str, float] = {}
    for h in engine_spans(events):
        s, e = max(h["start_ns"], lo), min(h["start_ns"] + h["dur_ns"], hi)
        if e > s:
            out[h["name"]] = out.get(h["name"], 0.0) + (e - s) / 1e9
    return out


# ---------------------------------------------------------------------------
# a traced run
# ---------------------------------------------------------------------------

def round_stage_maps(run) -> List[Dict[str, Tuple[Optional[str], str]]]:
    """``stage_map`` of the round program at each draft bucket the traced
    stretch ran, from its compiled text.  Compiling again after the
    window finds the programs set-up compiled (in memory or in the
    persistent cache)."""
    import jax
    import numpy as np
    from repro.core import spec_decode as sd
    eng = run.system.engine
    # the rounds of the stretch, and one on each side that can run in it
    st = run.tracer_state
    lo, hi = st["start"]["rounds"], st["stop"]["rounds"]
    ks = sorted({r["k"] for r in run.round_log[max(lo - 1, 0):hi + 1]})
    b = eng.serving.max_batch_size
    maps = []
    with jax.default_matmul_precision(eng.serving.matmul_precision):
        for k in ks:
            text = sd.spec_decode_round.lower(
                eng.pt, eng.pd, eng.cfg_t, eng.drafter, eng.spec, k,
                eng.state, np.zeros((b,), bool)).compile().as_text()
            maps.append(stage_map(text))
    return maps


def attribution(run) -> Dict:
    """The whole attribution of a traced run, computed once per run and
    printed once to standard error as ``bench: attribution {...}``:
    idle seconds per engine span, device ms per round execution of each
    stage, and wall (spans) and CPU (round log) ms per round of each
    engine phase."""
    cached = getattr(run, "_attribution", None)
    if cached is not None:
        return cached
    t = run.trace
    lo, hi = t["lo"], t["hi"]
    events = run.events
    out: Dict = {"window_s": t["window_s"],
                 "idle_s": t["window_s"] - t["busy_s"]}
    out["idle_by_span_s"] = (idle_by_span(events, lo, hi)
                             if t["chips"] else {})
    # a program without the stage scopes has nothing to attribute
    maps = [m for m in round_stage_maps(run)
            if any(st for st, _ in m.values())]
    stages = stage_seconds(events, lo, hi, maps)
    n = stages.get("executions", 0)
    out["round_executions"] = n
    out["stage_ms_per_round"] = ({k: 1000.0 * v / n for k, v in
                                  stages.items() if k != "executions"}
                                 if n else {})
    rounds = [r for r in run.stretch_rounds() if "plan_cpu_s" in r]
    spans = phase_seconds(events, lo, hi)
    nr = len(run.stretch_rounds())
    out["phase_wall_ms_per_round"] = ({k: 1000.0 * v / nr
                                       for k, v in spans.items()}
                                      if nr else {})
    out["phase_cpu_ms_per_round"] = (
        {p: 1000.0 * sum(r[f"{p}_cpu_s"] for r in rounds) / len(rounds)
         for p in PHASES} if rounds else {})
    run._attribution = out
    print("bench: attribution " + json.dumps(out, default=float),
          file=sys.stderr, flush=True)
    return out


def idle_share(run, names: Sequence[str]) -> Optional[float]:
    """Percent of the traced window that ``idle_by_span`` charged to the
    spans ``names``; None where the trace holds no engine span."""
    idle = attribution(run)["idle_by_span_s"]
    if not any(name != NO_SPAN for name in idle):
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / run.trace[
        "window_s"]
