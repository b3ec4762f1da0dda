"""One run of one cell: build the served system from the cell's files,
warm it, measure a window, read the metrics and decide ``correct``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in files of its own, found by the names ``BENCHMARK.json``
gives: ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py``.  This module holds no cell's numbers.

Two drivers share the set-up and the read-out:

* ``poisson`` mixes drive ``serving/frontend.py``'s ``ServingFrontend``
  (its driver thread over a pipelined ``ServingEngine``) from an
  open-loop generator in this thread.  A request's arrival time is the
  moment it was due, whenever the generator got to it; the generator's
  lateness is reported beside the result.
* ``offline`` mixes submit the whole queue to the engine and drive
  ``ServingEngine.pump()`` from this thread.  Set-up serves the first
  admission wave; the window then measures the steady batch.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

from bench import loadgen  # noqa: E402

WARM_ID = 1_000_000          # request ids of warm-up requests


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            cfg = next(c for c in bm["configs"] if c["name"] == w["config"])
            return {"workload": w, "config_entry": cfg}
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_config(entry_or_name, root: Path = ROOT) -> Dict:
    """A configuration file, by its ``BENCHMARK.json`` entry or by name."""
    if isinstance(entry_or_name, dict):
        return load_json(root / entry_or_name["file"])
    return load_json(BENCH / "configs" / f"{entry_or_name}.json")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cfg: Dict):
    return load_module(BENCH / "references" / f"{cfg['architecture']}.py")


def metric_reader(name: str, directory: Path = BENCH / "metrics"):
    return load_module(directory / f"{name}.py").read


def setup_process() -> None:
    """What every entry point of the benchmark does before it builds
    anything: JAX's persistent compilation cache goes to ``.jax_cache``
    inside this checkout (whatever the environment said, so that two
    checkouts never share one), and every program is kept there, the
    engine's small eager ones too, so that a later run of the cell loads
    and does not compile them.  The cache is unbounded: a size limit makes
    every write take a file lock and scan the whole directory, which with
    the thousands of small programs a cell warms stalled set-up for tens
    of minutes."""
    import os
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def log(t_process: float, what: str) -> None:
    """A progress line on standard error: seconds since the process
    started, and what set-up has just finished."""
    print(f"bench: {time.monotonic() - t_process:8.1f} s  {what}",
          file=sys.stderr, flush=True)


def peaks_for(kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(cfg: Dict):
    from repro.core.config import ModelConfig
    if cfg["architecture"] != "llama":
        raise ValueError(f"no engine mapping for {cfg['architecture']!r}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        head_dim=int(cfg["head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        source=cfg["source"])


def seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for each use of the run's ``--seed``."""
    s = np.random.SeedSequence(int(seed)).generate_state(5)
    names = ("target", "draft", "engine", "traffic", "sample")
    return {n: int(v) & 0x7FFFFFFF for n, v in zip(names, s)}


@dataclasses.dataclass
class System:
    cfg: Dict
    weights: Any
    draft_weights: Any
    engine: Any
    serving: Any


def build(cfg: Dict, seed: int) -> System:
    import jax
    from repro.core.config import ServingConfig, SpecDecodeConfig
    from repro.serving.engine import ServingEngine

    sd = seeds(seed)
    ref = reference_module(cfg)
    srv, spec_c = cfg["serving"], cfg["spec"]
    pt = ref.init_weights(cfg, jax.random.PRNGKey(sd["target"]))
    pd, mcfg_d = None, None
    if spec_c["drafter"] == "model":
        dcfg = load_config(cfg["draft"]) if isinstance(cfg["draft"], str) \
            else cfg["draft"]
        pd = reference_module(dcfg).init_weights(
            dcfg, jax.random.PRNGKey(sd["draft"]))
        mcfg_d = model_config(dcfg)
    b, s, bs = (int(srv["max_batch_size"]), int(srv["max_seq_len"]),
                int(srv["kv_block_size"]))
    serving = ServingConfig(
        max_batch_size=b, max_seq_len=s, paged_kv=True, kv_block_size=bs,
        num_kv_blocks=int(b * (s // bs) * float(srv["pool_fraction"])),
        pipelined=bool(srv["pipelined"]), kv_quant=srv["kv_quant"],
        prefix_caching=bool(srv["prefix_caching"]),
        matmul_precision=srv["matmul_precision"])
    spec = SpecDecodeConfig(policy=spec_c["policy"],
                            drafter=spec_c["drafter"],
                            temperature=float(spec_c["temperature"]))
    eng = ServingEngine(pt, model_config(cfg), pd, mcfg_d, spec, serving,
                        seed=sd["engine"])
    return System(cfg, pt, pd, eng, serving)


@functools.lru_cache(maxsize=None)
def request_class():
    from repro.serving.request import Request

    class WatchedRequest(Request):
        """A request whose token callback first calls the benchmark's
        ``watch(request, token)``, whoever sets the callback (the
        front-end sets its stream's on submission)."""

        def __init__(self, *a, watch=None, **k):
            self.__dict__["_watch"] = watch
            super().__init__(*a, **k)

        @property
        def on_token(self):
            return self.__dict__.get("_cb")

        @on_token.setter
        def on_token(self, fn):
            watch = self.__dict__.get("_watch")
            if watch is None or fn is None:
                self.__dict__["_cb"] = watch or fn
                return

            def cb(r, t):
                watch(r, t)
                fn(r, t)
            self.__dict__["_cb"] = cb

    return WatchedRequest


def make_request(i: int, prompt: List[int], max_new: int, due: float,
                 watch: Optional[Callable] = None):
    return request_class()(request_id=i, prompt=list(prompt),
                           max_new_tokens=max_new, eos_token_id=None,
                           arrival_time=due, watch=watch)


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------

def round_buckets(eng) -> List[int]:
    """Draft buckets the cell's policy can dispatch: every one from
    ``sl_min`` to the policy's largest when greedy rounds pick from the
    SL predictions, only the largest when sampled rounds dispatch at it."""
    top = eng.policy.max_bucket()
    if eng.spec.temperature > 0.0 and eng.serving.pipelined:
        return [top]
    return list(range(min(eng.spec.sl_min, top), top + 1))


def run_engine_until_idle(eng) -> None:
    while eng.has_pending_work():
        eng.pump()
    eng.drain()


def reset_counts(system: System, prompt_lengths: List[int],
                 rows: int) -> Dict[bool, range]:
    """Block counts whose ``kv_pos`` reset the window can ask for, derived
    from the traffic, the largest draft bucket and the block size.  The
    engine resets freshly allocated blocks with eager operations shaped
    by their number (about nine small programs per count), and a program
    compiled inside its precision context is another than one compiled
    outside it.  Admission resets inside the context: up to ``rows``
    requests admitted at once, each with the blocks of its prompt, the
    longest planned at most.  Before a round the engine grows each
    running row's allocation outside the context, from what the last
    collect kept to the next round's write extent, ``K + 1`` tokens
    further: at most ``ceil((K_max + 1) / block)`` blocks a row.  A
    readmission after a preemption, which only a dry pool causes, is not
    warmed; ``window_compiles`` shows it.  Keyed by whether the count runs
    inside the context."""
    srv = system.serving
    bs = srv.kv_block_size
    per_row = -(-(system.engine.policy.max_bucket() + 1) // bs)
    return {True: range(1, rows * -(-max(prompt_lengths) // bs) + 1),
            False: range(1, srv.max_batch_size * per_row + 1)}


def precompile(system: System, buckets: Dict[int, int], rows: int,
               counts: Dict[bool, range], workers: int = 6,
               progress: Callable[[str], None] = lambda what: None
               ) -> Dict[str, float]:
    """Compile, in ``workers`` threads, every program the window can run:
    the round program of every draft bucket, the prefill program of every
    (rows, prompt bucket) pair for the target and for a mirrored draft,
    and the block reset of every count, inside and outside the precision
    context as the engine runs it.  The engine's own calls then find
    them compiled, and the persistent cache keeps them for the next run.
    Returns the wall seconds, the compile seconds summed over jobs and
    the longest job."""
    import contextlib
    import jax
    from concurrent.futures import ThreadPoolExecutor
    from repro.core import prefill as prefill_lib
    from repro.core import spec_decode as sd
    from repro.models import cache as cache_lib
    eng = system.engine
    prec = system.serving.matmul_precision
    b = system.serving.max_batch_size
    width = system.serving.blocks_per_seq()
    i32 = np.int32

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, i32)

    models = [(eng.pt, eng.cfg_t, eng.state.target_cache)]
    if eng.drafter.mirrors_kv():
        models.append((eng.pd, eng.cfg_d, eng.state.draft_cache))
    programs = [("round", k) for k in round_buckets(eng)]
    programs += [("prefill", (m, r, bucket)) for m in range(len(models))
                 for bucket in buckets for r in range(rows, 0, -1)]
    resets = [("reset", (n, inside)) for inside, ns in counts.items()
              for n in ns]
    kv_pos = eng.state.target_cache["kv_pos"]

    def compile_one(job):
        t = time.monotonic()
        kind, arg = job
        if kind == "reset":
            n, inside = arg
            with (jax.default_matmul_precision(prec) if inside
                  else contextlib.nullcontext()):
                jax.block_until_ready(
                    cache_lib.reset_blocks(kv_pos, list(range(n))))
            return time.monotonic() - t
        with jax.default_matmul_precision(prec):
            if kind == "round":
                sd.spec_decode_round.lower(
                    eng.pt, eng.pd, eng.cfg_t, eng.drafter, eng.spec, arg,
                    eng.state, np.zeros((b,), bool)).compile()
            else:
                m, r, bucket = arg
                params, cfg, cache = models[m]
                prefill_lib.prefill_paged_rows.lower(
                    params, cfg, cache["k"], cache["v"], cache["kv_pos"],
                    spec((r, width)), spec((r, bucket)), spec((r,)),
                    plan=None, k_scale=cache.get("k_scale"),
                    v_scale=cache.get("v_scale")).compile()
        return time.monotonic() - t

    t0 = time.monotonic()
    took = []
    with ThreadPoolExecutor(max_workers=workers) as ex:
        for what, jobs in (("round and prefill programs", programs),
                           ("block resets", resets)):
            took += ex.map(compile_one, jobs)
            progress(f"{what} compiled: {len(jobs)}")
    return {"compile_s": time.monotonic() - t0,
            "compile_job_s": sum(took), "compile_max_job_s": max(took),
            "compile_jobs": len(took)}


def warm(system: System, prompt_lengths: List[int], rows: int,
         rng: np.random.Generator,
         progress: Callable[[str], None] = lambda what: None
         ) -> Dict[str, float]:
    """Compile every program the window will use (``precompile``), then
    run each round bucket once (an all-idle round) and each prefill bucket
    of the cell's prompt lengths for 1..``rows`` requests admitted
    together, through the engine's own admission path."""
    import jax
    import jax.numpy as jnp
    eng = system.engine
    buckets = loadgen.prompt_buckets(prompt_lengths)
    counts = reset_counts(system, prompt_lengths, rows)
    info = precompile(system, buckets, rows, counts, progress=progress)
    info.update(reset_counts_inside=len(counts[True]),
                reset_counts_outside=len(counts[False]))
    progress("{compile_job_s:.1f} s of compiling in {compile_jobs} jobs, "
             "longest {compile_max_job_s:.1f} s".format(**info))
    t1 = time.monotonic()
    idle = jnp.zeros((system.serving.max_batch_size,), bool)
    with jax.default_matmul_precision(system.serving.matmul_precision):
        for k in round_buckets(eng):
            # the round returns a whole new state (pools included): drop
            # it before the next, so that warm-up holds one at a time
            jax.block_until_ready(eng._round_fn(k)(eng.state, idle))
    t2 = time.monotonic()
    progress("round programs run")
    vocab = int(system.cfg["vocab_size"])
    rid = WARM_ID
    for bucket, n in buckets.items():
        for r in range(1, rows + 1):
            for _ in range(r):
                eng.submit(make_request(rid, rng.integers(0, vocab, n).tolist(),
                                        2, time.monotonic()))
                rid += 1
            run_engine_until_idle(eng)
    return {**info, "warm_rounds_s": t2 - t1,
            "warm_prefill_s": time.monotonic() - t2}


# ---------------------------------------------------------------------------
# compile counting
# ---------------------------------------------------------------------------

class CompileLog:
    """Programs compiled or loaded from the persistent cache, with the
    seconds each took, while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.events: List = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == self.EVENT:
            self.events.append((kw.get("fun_name", "?"), duration))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    t_end: float = 0.0
    requests: List[Any] = dataclasses.field(default_factory=list)
    lateness: List[float] = dataclasses.field(default_factory=list)
    round_lo: int = 0
    round_hi: int = 0
    emitted_lo: int = 0
    emitted_hi: int = 0
    pump_cpu_s: float = 0.0
    stretch: Optional[Dict] = None
    kv_snapshots: List[Dict] = dataclasses.field(default_factory=list)
    unfinished: int = 0
    drain_s: float = 0.0


class Tracer:
    """Starts and stops the profiler over the traced stretch and notes
    where the engine stood at both ends."""

    def __init__(self, directory: Path, eng, requests: List[Any]):
        self.dir, self.eng, self.requests = directory, eng, requests
        self.state: Dict = {}

    def _mark(self) -> Dict:
        return {"t": time.monotonic(), "rounds": len(self.eng.round_log),
                "outputs": {id(r): len(r.output) for r in self.requests}}

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.state["start"] = self._mark()

    def stop(self) -> None:
        import jax
        self.state["stop"] = self._mark()
        jax.profiler.stop_trace()


def annotate_engine(eng) -> None:
    """Host spans around the engine's three phases, for the traced run
    only: each call is wrapped in a profiler annotation named
    ``bench.<phase>``, on the instance."""
    import jax
    for phase in ("plan", "dispatch", "collect"):
        fn = getattr(eng, phase)

        def wrapped(*a, _fn=fn, _name=f"bench.{phase}", **k):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **k)
        setattr(eng, phase, wrapped)


class KVSnapshots:
    """Copies, on the device, of the keys and values the target pool holds
    for a request over its committed positions.  The copy takes a fixed
    number of blocks (the block-table width), so one gather program
    serves every request; the host trims it later."""

    def __init__(self, eng):
        import jax
        import jax.numpy as jnp
        self.eng = eng
        self.width = eng.serving.blocks_per_seq()
        self.bs = eng.serving.kv_block_size
        self.gather = jax.jit(lambda pool, ids: jnp.take(pool, ids, axis=1))
        self.taken: List[Dict] = []

    def take(self, r) -> None:
        import jax.numpy as jnp
        n = min(r.cache_len, len(r.prompt) + len(r.output) - 1)
        if n <= 0 or not r.block_ids:
            return
        ids = list(r.block_ids[:-(-n // self.bs)])
        ids += [ids[-1]] * (self.width - len(ids))
        ids = jnp.asarray(ids, jnp.int32)
        tc = self.eng.state.target_cache
        k, v = self.gather(tc["k"], ids), self.gather(tc["v"], ids)
        for a in (k, v):
            a.copy_to_host_async()
        self.taken.append({"request": r, "n": n, "k": k, "v": v})

    def warm(self) -> None:
        """The programs ``take`` runs, the list's conversion included."""
        import jax
        import jax.numpy as jnp
        ids = jnp.asarray([0] * self.width, jnp.int32)
        tc = self.eng.state.target_cache
        jax.block_until_ready(self.gather(tc["k"], ids))

    def host(self) -> List[Dict]:
        """The copies on the host, trimmed to their committed positions.
        Lets go of the engine: the requests' callbacks still hold this
        object, and the program's state must be freeable after the
        window."""
        self.eng = None
        out = []
        for s in self.taken:
            k, v = np.asarray(s["k"]), np.asarray(s["v"])
            shape = (k.shape[0], -1) + k.shape[3:]
            out.append({"request": s["request"], "n": s["n"],
                        "k": k.reshape(shape)[:, :s["n"]],
                        "v": v.reshape(shape)[:, :s["n"]]})
        self.taken = []
        return out


def finish_watch(snaps: KVSnapshots, chosen: set) -> Callable:
    """Token callback that copies a chosen request's keys and values when
    its last token is delivered, before its blocks go back to the pool."""
    def watch(r, tok):
        if r.request_id in chosen and len(r.output) == r.max_new_tokens:
            snaps.take(r)
    return watch


def choose_checked(planned, count: int, rng: np.random.Generator) -> set:
    """Indices of the requests whose keys and values are checked: the
    longest, and others drawn from the seed."""
    if not planned:
        return set()
    longest = max(range(len(planned)), key=lambda i: len(planned[i].prompt)
                  + planned[i].max_new_tokens)
    rest = [i for i in rng.permutation(len(planned)) if i != longest]
    return {longest, *rest[:count - 1]}


def drive_open_loop(system: System, planned, seconds: float,
                    tracer: Optional[Tracer], trace_at, kv_count: int,
                    rng: np.random.Generator, compiles: "CompileLog",
                    settle_s: float = 120.0) -> Window:
    from repro.serving.frontend import ServingFrontend
    eng = system.engine
    fe = ServingFrontend(eng)
    snaps = KVSnapshots(eng)
    snaps.warm()
    watch = finish_watch(snaps, {planned[i].index for i in
                                 choose_checked(planned, kv_count, rng)})
    reqs = [make_request(p.index, p.prompt, p.max_new_tokens, 0.0, watch)
            for p in planned]
    if tracer is not None:
        tracer.requests = reqs
    events = [(p.due_s, "submit", i) for i, p in enumerate(planned)]
    if tracer is not None:
        events += [(trace_at[0], "trace_start", -1),
                   (trace_at[1], "trace_stop", -1)]
    events.sort(key=lambda e: (e[0], e[1] != "trace_stop"))
    w = Window(t0=0.0, seconds=seconds, round_lo=len(eng.round_log),
               emitted_lo=eng.emitted_total)
    w.t0 = time.monotonic()
    compiles.active = True
    fe.start()
    for at, what, i in events:
        due = w.t0 + at
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if what == "submit":
            r = reqs[i]
            r.arrival_time = due
            fe.submit_request(r)
            w.lateness.append(time.monotonic() - due)
        elif what == "trace_start":
            tracer.start()
        else:
            tracer.stop()
    delay = w.t0 + seconds - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    w.t_end = time.monotonic()
    compiles.active = False
    # the window has closed: no request is due after it; every request of
    # the window is let finish
    w.round_hi, w.emitted_hi = len(eng.round_log), eng.emitted_total
    deadline = time.monotonic() + settle_s
    while time.monotonic() < deadline and not all(r.done for r in reqs):
        time.sleep(0.005)
    w.drain_s = time.monotonic() - w.t_end
    fe.stop()
    w.unfinished = sum(not r.done for r in reqs)
    w.requests = reqs
    w.kv_snapshots = snaps.host()
    return w


def drive_offline(system: System, planned, seconds: float,
                  tracer: Optional[Tracer], trace_at, kv_count: int,
                  rng: np.random.Generator, compiles: "CompileLog") -> Window:
    """Set-up serves the first admission wave of the queue (all due at
    once), admitted ``serving.wave_rows`` requests at a time where the
    configuration sets it (a prefill computes logits at every position,
    so a group of rows admitted together must fit the chip); the window
    then pumps the engine for ``seconds``."""
    eng = system.engine
    t_q = time.monotonic()
    reqs = [make_request(p.index, p.prompt, p.max_new_tokens, t_q)
            for p in planned]
    wave = int(system.cfg["serving"].get("wave_rows", len(reqs)))
    for lo in range(0, len(reqs), wave):
        for r in reqs[lo:lo + wave]:
            eng.submit(r)
        if lo < system.serving.max_batch_size:
            eng.pump()
    for _ in range(2):               # wave prefilled, its first round back
        eng.pump()
    if tracer is not None:
        tracer.requests = reqs
    w = Window(t0=time.monotonic(), seconds=seconds,
               round_lo=len(eng.round_log), emitted_lo=eng.emitted_total)
    marks = ([(trace_at[0], tracer.start), (trace_at[1], tracer.stop)]
             if tracer is not None else [])
    end = w.t0 + seconds
    compiles.active = True
    while time.monotonic() < end and eng.has_pending_work():
        if marks and time.monotonic() - w.t0 >= marks[0][0]:
            marks.pop(0)[1]()
            continue
        t = time.thread_time()
        eng.pump()
        w.pump_cpu_s += time.thread_time() - t
    for _, mark in marks:
        mark()
    w.t_end = time.monotonic()
    compiles.active = False
    w.round_hi, w.emitted_hi = len(eng.round_log), eng.emitted_total
    eng.drain()
    snaps = KVSnapshots(eng)
    live = [r for r in reqs if not r.done and r.slot is not None]
    for i in choose_checked([planned[r.request_id] for r in live],
                            kv_count, rng):
        snaps.take(live[i])
    w.kv_snapshots = snaps.host()
    w.requests = reqs
    return w


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """The q-th percentile, linear between order statistics; a missing
    value (a failed request) counts as infinitely late."""
    if not values:
        return float("nan")
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(cell: Dict, w: Window, setup_s: float) -> Dict[str, float]:
    out = {"setup_s": setup_s}
    mix = cell["mix"]
    if mix["arrival"] == "offline":
        out["output_tok_s"] = ((w.emitted_hi - w.emitted_lo)
                               / (w.t_end - w.t0))
        return out
    inf = float("inf")
    ttft = [(r.first_token_time - r.arrival_time)
            if r.first_token_time is not None else inf for r in w.requests]
    tpot = []
    for r in w.requests:
        if r.done and r.finish_time is not None and len(r.output) > 1 \
                and r.first_token_time is not None \
                and r.state.value == "finished":
            tpot.append((r.finish_time - r.first_token_time)
                        / (len(r.output) - 1))
        else:
            tpot.append(inf)
    out["ttft_p95_s"] = percentile(ttft, 95)
    out["tpot_p95_s"] = percentile(tpot, 95)
    return out


class RunRecord:
    """What a per-layer metric reader gets: the cell, the window, the
    engine's round log and requests, the reduced trace and the peaks of
    the device.  A reader returns a number, or None where it finds
    nothing to read."""

    def __init__(self, cell: Dict, window: Window, system: System,
                 tracer: Optional[Tracer], chips: int, device: Dict,
                 peaks: Dict):
        self.cell, self.window, self.system = cell, window, system
        self.cfg = cell["cfg"]
        self.chips, self.peaks = chips, peaks
        eng = system.engine
        self.round_log = list(eng.round_log)
        self.max_batch = eng.serving.max_batch_size
        self.kv_blocks_total = eng.scheduler.kv_blocks_total()
        self.tracer_state = dict(tracer.state) if tracer is not None else {}
        self.trace_dir = tracer.dir if tracer is not None else None
        self.events: List[Dict] = []
        self.trace: Optional[Dict] = None

    def load_trace(self) -> None:
        from bench import traces as trace_lib
        self.events = trace_lib.load(str(self.trace_dir))
        self.trace = trace_lib.reduce(self.events)

    # -- the window -----------------------------------------------------
    def window_rounds(self) -> List[Dict]:
        return self.round_log[self.window.round_lo:self.window.round_hi]

    def window_requests(self) -> List[Any]:
        return self.window.requests

    # -- the traced stretch ---------------------------------------------
    def stretch_rounds(self) -> List[Dict]:
        st = self.tracer_state
        if "start" not in st or "stop" not in st:
            return []
        return self.round_log[st["start"]["rounds"]:st["stop"]["rounds"]]

    def stretch_tokens(self):
        """(emitted decode tokens, their attended contexts summed,
        prefilled prompt tokens, their causal contexts summed) in the
        traced stretch.  A request's first token comes out of its prefill
        and is counted with the prompt."""
        st = self.tracer_state
        a, b = st["start"], st["stop"]
        dec = dec_ctx = pre = pre_ctx = 0
        for r in self.window.requests:
            lo = a["outputs"].get(id(r), 0)
            hi = b["outputs"].get(id(r), 0)
            p = len(r.prompt)
            if lo == 0 and hi > 0:
                pre += p
                pre_ctx += p * (p + 1) // 2
                lo = 1
            for i in range(lo, hi):
                dec += 1
                dec_ctx += p + i
        return dec, dec_ctx, pre, pre_ctx

    def program_seconds(self, substring: str):
        from bench import traces as trace_lib
        t = self.trace
        return trace_lib.program_seconds(self.events, substring, t["lo"],
                                         t["hi"])

    def kernel_events(self, substrings):
        from bench import traces as trace_lib
        t = self.trace
        return trace_lib.kernel_events(self.events, substrings, t["lo"],
                                       t["hi"])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, require_tpu: bool = True,
             overrides: Optional[Dict] = None, root: Path = ROOT,
             trace_dir: Optional[Path] = None, control: bool = False
             ) -> Dict:
    """One run; returns the result line's object (and, under ``_info``,
    what the run prints on an earlier line).  ``overrides`` (tests only)
    replaces configuration and mix entries, to run a cell's control flow
    at a CPU-sized shape."""
    import jax
    devices = jax.devices()
    bm = load_benchmark(root)
    wl = find_cell(bm, workload)["workload"]
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SystemExit(f"bench: needs a TPU, JAX found "
                             f"{devices[0].platform}")
        if len(devices) < int(wl["chips"]):
            raise SystemExit(f"bench: the cell needs {wl['chips']} chips, "
                             f"JAX found {len(devices)}")
    cfg = load_config(find_cell(bm, workload)["config_entry"], root)
    mix = loadgen.load_mix(wl["traffic"], root / "bench" / "traffic")
    if overrides:
        cfg = _merge(cfg, overrides.get("config", {}))
        mix = _merge(mix, overrides.get("mix", {}))
    cell = {"workload": wl, "cfg": cfg, "mix": mix}
    sd = seeds(seed)
    rng = np.random.default_rng(sd["sample"])
    compiles = CompileLog()

    system = build(cfg, seed)
    log(t_process, "weights and engine built")
    planned = loadgen.plan(mix, seconds, sd["traffic"],
                           int(cfg["vocab_size"]))
    warm_info = warm(system, [len(p.prompt) for p in planned],
                     int(cfg["serving"]["warm_rows"]),
                     np.random.default_rng(sd["traffic"] ^ 0x5EED),
                     functools.partial(log, t_process))
    log(t_process, "prefill programs warm")
    tracer, trace_at = None, None
    if trace:
        tracer = Tracer(trace_dir or (root / ".bench_runs" / "trace"),
                        system.engine, [])
        _clear(tracer.dir)
        annotate_engine(system.engine)
        stretch = min(float(cfg.get("trace_seconds", 3.0)), seconds / 2)
        first = max(0.0, seconds / 2 - stretch / 2)
        trace_at = (first, first + stretch)
    check_c = cfg["check"]
    drive = drive_offline if mix["arrival"] == "offline" else drive_open_loop
    w = drive(system, planned, seconds, tracer, trace_at,
              int(check_c["kv_requests"]), rng, compiles)
    setup_s = w.t0 - t_process
    log(t_process, "window closed")
    blocks = system.engine.scheduler.kv_blocks_total()
    used = [r["kv_blocks_in_use"] for r in
            system.engine.round_log[w.round_lo:w.round_hi]]

    dev = devices[0]
    n_chips = int(wl["chips"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_chips,
              "memory_peak_bytes": int(max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices[:n_chips]))}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        run = RunRecord(cell, w, system, tracer, n_chips, device,
                        (overrides or {}).get("peaks")
                        or peaks_for(device["kind"]))
        run.load_trace()
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
        for m in bm["per_layer"]:
            if wl["name"] not in m.get("workloads", [wl["name"]]):
                continue
            value = metric_reader(m["name"], root / "bench" / "metrics")(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        del run
        tracer.eng = None
        if not trace_dir:
            _clear(tracer.dir)
    else:
        e2e = end_to_end(cell, w, setup_s)
        for m in bm["end_to_end"]:
            if wl["name"] not in m.get("workloads", [wl["name"]]):
                continue
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the window is over and memory has been read: free the program's
    # state, then judge what it served against the reference
    from bench import check
    items = check.served_items(w, rng, int(check_c["token_requests"]),
                               int(check_c["min_tokens"]))
    w.kv_snapshots = []
    weights = system.weights
    system.engine = system.draft_weights = system.weights = None
    gc.collect()
    numbers = check.numbers(reference_module(cfg), weights, cfg, items,
                            float(cfg["spec"]["temperature"]))
    limits = check_c["limits"]
    control_numbers = (check.control_numbers(
        reference_module(cfg), weights, cfg, items,
        float(cfg["spec"]["temperature"]), sd["sample"]) if control else None)
    if mix["arrival"] == "offline":
        attempted = sum(r.admit_time is not None for r in w.requests)
    else:
        attempted = len(w.requests)
    failed = w.unfinished + sum(r.state.value == "rejected"
                                for r in w.requests)
    finite = all(math.isfinite(m["value"]) for m in metrics.values())
    for m in metrics.values():
        if not math.isfinite(m["value"]):
            m["value"] = None
    log(t_process, "reference compared")
    correct = check.within(numbers, limits) and failed == 0 and finite
    info = {"lateness_p99_s": percentile(w.lateness, 99) if w.lateness
            else 0.0,
            "lateness_max_s": max(w.lateness) if w.lateness else 0.0,
            "window_compiles": len(compiles.events),
            "window_compile_s": sum(d for _, d in compiles.events),
            "window_compiled": sorted({n for n, _ in compiles.events})[:20],
            "requests": len(w.requests), "unfinished": w.unfinished,
            "drain_s": w.drain_s,
            "rounds": w.round_hi - w.round_lo,
            "checked_requests": len(items),
            "checked_tokens": sum(len(i["output"]) for i in items),
            "checked_kv": sum("k" in i for i in items),
            "pool_blocks": blocks,
            "pool_fill_mean": sum(used) / len(used) / blocks if used else 0.0,
            "pool_fill_max": max(used) / blocks if used else 0.0,
            **warm_info}
    if control_numbers is not None:
        info["control"] = control_numbers
        info["token_fault"] = check.token_fault_numbers(
            reference_module(cfg), weights, cfg, items,
            float(cfg["spec"]["temperature"]))
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": v, "limit": limits[k]}
                       for k, v in numbers.items()}
    result["_info"] = info
    return result


def _merge(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _clear(d: Path) -> None:
    import shutil
    if d.exists():
        shutil.rmtree(d)
    d.mkdir(parents=True, exist_ok=True)
