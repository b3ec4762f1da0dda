"""How ``correct`` is decided: what the timed path served, against the
plain float32 reference.

After the window, two kinds of evidence are kept:

* served tokens: a sample, drawn from the seed, of the requests that
  finished, with the longest among them, until ``min_tokens`` tokens are
  in it; and every live request whose keys and values were copied at
  the close;
* keys and values: the target pool's entries over the committed
  positions of ``kv_requests`` requests (the longest and others drawn
  from the seed), copied when an open-loop request delivers its last
  token, or at the close for requests an offline window leaves running.

The reference then runs once over each request's prompt and served
tokens, and gives the numbers compared:

* ``kv_err``: over every checked request and layer, the largest
  difference between a key (or value) the program stored and the
  reference's, in units of the root mean square of that layer's
  reference keys (values).  It covers prefill into the paged pool, the
  verify forward through the paged kernel, and commit: an entry at a
  position the program never committed, or computed from another
  token, or in a lower precision, moves it.
* ``token_gap`` (greedy cells): the widest gap by which a served token's
  logit lies below the reference's best at that position.
* ``sample_z`` (sampled cells): served tokens are draws from the
  target's distribution at the configuration's temperature, so
  ``sum(s[x] - E_p s) / sqrt(sum Var_p s)`` over all served positions,
  with ``s`` the tempered reference logits, is a standard normal; its
  absolute value is compared.  A token altered where it is produced, or
  a draft accepted that the target would reject, pulls it far from 0.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def served_items(window, rng: np.random.Generator, max_requests: int,
                 min_tokens: int) -> List[Dict]:
    items = []
    for s in window.kv_snapshots:
        r = s["request"]
        items.append({"prompt": list(r.prompt), "output": list(r.output),
                      "n": s["n"], "k": s["k"], "v": s["v"]})
    done = [r for r in window.requests if r.state.value == "finished"
            and r.output]
    if done:
        done.sort(key=lambda r: -(len(r.prompt) + len(r.output)))
        order = [done[0]] + [done[i] for i in
                             rng.permutation(np.arange(1, len(done)))]
        tokens = 0
        for r in order[:max_requests]:
            items.append({"prompt": list(r.prompt), "output": list(r.output)})
            tokens += len(r.output)
            if tokens >= min_tokens:
                break
    return items


def _layer_err(ref, got) -> float:
    """max over layers of max|got - ref| / rms(ref), [L, n, ...] arrays."""
    import jax.numpy as jnp
    ref = jnp.asarray(ref)
    got = jnp.asarray(got)
    axes = tuple(range(1, ref.ndim))
    rms = jnp.sqrt(jnp.mean(ref * ref, axis=axes))
    err = jnp.max(jnp.abs(got - ref), axis=axes)
    return float(jnp.max(err / rms))


def _token_stats(logits, output, temperature: float):
    """(widest gap below the best, sum of s[x] - E_p s, sum of Var_p s)."""
    import jax
    import jax.numpy as jnp
    tok = jnp.asarray(output, jnp.int32)
    rows = jnp.arange(tok.shape[0])
    picked = logits[rows, tok]
    gap = float(jnp.max(jnp.max(logits, -1) - picked))
    if temperature <= 0.0:
        return gap, 0.0, 0.0
    s = logits / temperature
    p = jax.nn.softmax(s, axis=-1)
    mean = jnp.sum(p * s, -1)
    var = jnp.sum(p * (s - mean[:, None]) ** 2, -1)
    return gap, float(jnp.sum(s[rows, tok] - mean)), float(jnp.sum(var))


def _positions(item):
    p0 = len(item["prompt"]) - 1
    return slice(p0, p0 + len(item["output"]))


def numbers(ref, weights, cfg: Dict, items: List[Dict],
            temperature: float) -> Dict[str, float]:
    """The compared numbers for what the program served."""
    import jax
    kv_err, gap, zsum, vsum = 0.0, 0.0, 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for it in items:
            toks = it["prompt"] + it["output"]
            logits, ks, vs = ref.forward(weights, cfg, toks[:-1], "f32")
            g, z, v = _token_stats(logits[_positions(it)], it["output"],
                                   temperature)
            gap, zsum, vsum = max(gap, g), zsum + z, vsum + v
            if "k" in it:
                n = it["n"]
                kv_err = max(kv_err, _layer_err(ks[:, :n], it["k"]),
                             _layer_err(vs[:, :n], it["v"]))
    out = {"kv_err": kv_err}
    if temperature <= 0.0:
        out["token_gap"] = gap
    else:
        out["sample_z"] = abs(zsum) / math.sqrt(vsum) if vsum > 0 else 0.0
    return out


def control_numbers(ref, weights, cfg: Dict, items: List[Dict],
                    temperature: float, seed: int) -> Dict[str, float]:
    """The same numbers with the reference computed one precision step
    below the configuration's (``bf16x3``) put in the program's place:
    its keys and values replace the pool's, and the tokens it puts first
    (greedy) or draws with the seed's Gumbel noise (sampled) replace the
    served ones, at every position of the same prompts and tokens."""
    import jax
    import jax.numpy as jnp
    kv_err, gap, zsum, vsum = 0.0, 0.0, 0.0, 0.0
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        for i, it in enumerate(items):
            toks = it["prompt"] + it["output"]
            logits, ks, vs = ref.forward(weights, cfg, toks[:-1], "f32")
            c_logits, c_ks, c_vs = ref.forward(weights, cfg, toks[:-1],
                                               "bf16x3")
            lg, cl = logits[_positions(it)], c_logits[_positions(it)]
            if temperature <= 0.0:
                ctoks = jnp.argmax(cl, -1)
            else:
                g = jax.random.gumbel(jax.random.fold_in(key, i), cl.shape)
                ctoks = jnp.argmax(cl / temperature + g, -1)
            g_, z, v = _token_stats(lg, np.asarray(ctoks), temperature)
            gap, zsum, vsum = max(gap, g_), zsum + z, vsum + v
            if "k" in it:
                n = it["n"]
                kv_err = max(kv_err, _layer_err(ks[:, :n], c_ks[:, :n]),
                             _layer_err(vs[:, :n], c_vs[:, :n]))
    out = {"kv_err": kv_err}
    if temperature <= 0.0:
        out["token_gap"] = gap
    else:
        out["sample_z"] = abs(zsum) / math.sqrt(vsum) if vsum > 0 else 0.0
    return out


def token_fault_numbers(ref, weights, cfg: Dict, items: List[Dict],
                        temperature: float) -> Dict[str, float]:
    """The numbers with a fault planted in what was served: every served
    token replaced by the next token id, as if altered where it was
    produced.  Keys and values are left out (they were not altered)."""
    v = int(cfg["vocab_size"])
    shifted = [{"prompt": it["prompt"],
                "output": [(t + 1) % v for t in it["output"]]}
               for it in items]
    out = numbers(ref, weights, cfg, shifted, temperature)
    del out["kv_err"]
    return out


def within(numbers_: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(k in limits and math.isfinite(v) and v <= limits[k]
               for k, v in numbers_.items())
