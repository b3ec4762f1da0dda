"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix file holds parameters only::

    {"arrival": "poisson", "rate_rps": 3.0,
     "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                       "min": 32, "max": 1536},
     "output_tokens": {"dist": "uniform", "min": 16, "max": 96}}

``arrival`` is ``poisson`` (open loop at ``rate_rps``, which the mix
must state: a poisson mix without a rate is refused) or ``offline``
(``queue`` requests, all due at t = 0; the prompt and output lengths of
the first ``fixed_head`` of them are the same for every seed, so that
the first admission wave, which set-up serves, runs the same prefill
programs and the window that follows does the same work).
A length distribution is ``uniform`` (inclusive integer range) or
``lognormal`` (``median``, ``sigma``), clipped to ``[min, max]``.

Every seed gets the same work in another order.  For a window of W
seconds an open-loop mix offers ``n = round(rate_rps * W)`` requests:
their prompt and output lengths are the distributions' quantiles at
``(i + 0.5) / n``, and their interarrival gaps the exponential
quantiles, scaled so that the n arrivals fill exactly W seconds.  The
seed permutes the three sets independently and draws the prompt tokens,
so two seeds differ in which request is long and when, never in how
much there is to do.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class PlannedRequest:
    """One request of a run: when it is due (seconds after the window
    opens), its prompt and how many tokens it asks for."""
    index: int
    due_s: float
    prompt: List[int]
    max_new_tokens: int


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    with open(directory / f"{name}.json") as f:
        return json.load(f)


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the distribution's quantiles
    ``(i + 0.5) / n``, clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
        x = np.floor(x)
    elif dist["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _gaps(rate_rps: float, n: int, seconds: float) -> np.ndarray:
    """n exponential interarrival quantiles, scaled to sum to
    ``seconds``: the first arrival is at t = 0 and the last inside the
    window."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate_rps
    return g * seconds / g.sum()


def rate(mix: Dict) -> float:
    """A poisson mix's offered rate; refused where the mix states none."""
    if "rate_rps" not in mix:
        raise ValueError("a poisson mix must state its rate_rps")
    return float(mix["rate_rps"])


def request_count(mix: Dict, seconds: float) -> int:
    if mix["arrival"] == "offline":
        return int(mix["queue"])
    return max(1, int(round(rate(mix) * seconds)))


def length_sets(mix: Dict, seconds: float):
    """The run's fixed multiset of (prompt lengths, output lengths), in
    quantile order — the same for every seed."""
    n = request_count(mix, seconds)
    return (_quantiles(mix["prompt_tokens"], n),
            _quantiles(mix["output_tokens"], n))


def _head_fixed(lengths: np.ndarray, head: int, rng: np.random.Generator,
                salt: int) -> np.ndarray:
    """The lengths in the seed's order, but for the first ``head``, which
    are the same for every seed."""
    if not head:
        return rng.permutation(lengths)
    fixed = np.random.default_rng(salt).permutation(lengths)
    return np.concatenate([fixed[:head], rng.permutation(fixed[head:])])


def plan(mix: Dict, seconds: float, seed: int,
         vocab_size: int) -> List[PlannedRequest]:
    """The run's requests, in due order."""
    prompts, outputs = length_sets(mix, seconds)
    n = len(prompts)
    ss = np.random.SeedSequence(seed)
    r_prompt, r_out, r_gap, r_tok = (np.random.default_rng(s)
                                     for s in ss.spawn(4))
    head = int(mix.get("fixed_head", 0))
    prompts = _head_fixed(prompts, head, r_prompt, 0)
    outputs = _head_fixed(outputs, head, r_out, 1)
    if mix["arrival"] == "offline":
        due = np.zeros(n)
    elif mix["arrival"] == "poisson":
        gaps = r_gap.permutation(_gaps(rate(mix), n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    return [PlannedRequest(
        index=i, due_s=float(due[i]),
        prompt=r_tok.integers(0, vocab_size, int(prompts[i])).tolist(),
        max_new_tokens=int(outputs[i])) for i in range(n)]


def prompt_buckets(lengths: Sequence[int], minimum: int = 16) -> Dict[int, int]:
    """The power-of-two prefill buckets these prompt lengths fall in, each
    with the longest length that lands in it (the warm-up's
    representative)."""
    out: Dict[int, int] = {}
    for n in lengths:
        b = max(minimum, 1 << math.ceil(math.log2(max(int(n), 1))))
        out[b] = max(out.get(b, 0), int(n))
    return dict(sorted(out.items()))
