"""Plain float32 reference of a llama-architecture decoder, and the
random weights the benchmark serves it with.

Independent of the program under test: nothing here imports it.  The
weights are laid out as the serving engine takes them (``embed``,
``final_norm``, ``lm_head``, and ``layers`` stacked on a leading layer
axis), and this module computes the same model from them in
straightforward ``jax.numpy``: RMSNorm with a ``1 + scale`` weight,
rotary embeddings on the two halves of each head, grouped-query causal
softmax attention, a SwiGLU MLP, and untied or tied output logits.

Every matrix product goes through :func:`dot`, in one of two modes:

* ``"f32"``: float32 at ``Precision.HIGHEST``, the configuration's own
  precision;
* ``"bf16x3"``: the control one precision step below it, three bfloat16
  products with float32 accumulation (hi*hi + hi*lo + lo*hi).  On a TPU
  this is what ``Precision.HIGH`` computes; written out, it computes
  the same on any backend.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: Dict) -> Dict[str, int]:
    return {"d": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
            "H": int(cfg["num_attention_heads"]),
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]), "f": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]), "Vp": padded_vocab(cfg)}


def padded_vocab(cfg: Dict) -> int:
    """Rows of the embedding table the engine expects: the vocabulary
    rounded up to a multiple of 128 with at least one spare row (the
    engine's padding token is ``vocab_size``)."""
    v = int(cfg["vocab_size"])
    return (v + 128) // 128 * 128


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _shapes(cfg: Dict) -> Dict:
    m = dims(cfg)
    d, L, H, KV, hd, f, Vp = (m[k] for k in ("d", "L", "H", "KV", "hd", "f",
                                             "Vp"))
    s = {"embed": ((Vp, d), 0.02),
         "final_norm": ((d,), 0.1),
         "layers": {
             "ln1": ((L, d), 0.1),
             "attn": {"wq": ((L, d, H, hd), d ** -0.5),
                      "wk": ((L, d, KV, hd), d ** -0.5),
                      "wv": ((L, d, KV, hd), d ** -0.5),
                      "wo": ((L, H, hd, d), (H * hd) ** -0.5)},
             "ln2": ((L, d), 0.1),
             "mlp": {"w_gate": ((L, d, f), d ** -0.5),
                     "w_up": ((L, d, f), d ** -0.5),
                     "w_down": ((L, f, d), f ** -0.5)}}}
    if not cfg["tie_word_embeddings"]:
        s["lm_head"] = ((d, Vp), 0.02)
    return s


def init_weights(cfg: Dict, key: jax.Array) -> Dict:
    """Normal weights, float32, made on the default device in one jitted
    call.  Matrices have standard deviation 1/sqrt(fan-in); the norm
    scales (used as ``1 + scale``) 0.1; embedding and output head 0.02,
    which puts the logits' spread near 0.02 * sqrt(hidden_size)."""
    shapes = _shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], tuple))

    def make(k):
        out = []
        for i, (shape, std) in enumerate(leaves):
            out.append(jax.random.normal(jax.random.fold_in(k, i), shape,
                                         jnp.float32) * std)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def dot(a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    if mode == "f32":
        return jnp.matmul(a, b, precision=HIGHEST)
    if mode == "bf16x3":
        def split(x):
            hi = x.astype(jnp.bfloat16)
            return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        (ah, al), (bh, bl) = split(a), split(b)

        def mm(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown precision mode {mode!r}")


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x [T, heads, hd] at positions 0..T-1; the first and second halves
    of each head are the two coordinates that rotate together."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _forward(w: Dict, tokens: jax.Array, cfg_items: Tuple,
             mode: str) -> Tuple[jax.Array, jax.Array, jax.Array]:
    cfg = dict(cfg_items)
    m = dims(cfg)
    d, H, KV, hd, V = m["d"], m["H"], m["KV"], m["hd"], m["V"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t = tokens.shape[0]
    g = H // KV
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        h = rmsnorm(x, p["ln1"], eps)
        q = dot(h, p["attn"]["wq"].reshape(d, H * hd), mode).reshape(t, H, hd)
        k = dot(h, p["attn"]["wk"].reshape(d, KV * hd), mode).reshape(t, KV,
                                                                      hd)
        v = dot(h, p["attn"]["wv"].reshape(d, KV * hd), mode).reshape(t, KV,
                                                                      hd)
        q, k = rope(q, theta), rope(k, theta)
        # query head j reads KV head j // g
        qg = q.reshape(t, KV, g, hd).transpose(1, 2, 0, 3)      # [KV,g,T,hd]
        kt = k.transpose(1, 2, 0)[:, None]                       # [KV,1,hd,T]
        s = dot(qg, kt, mode) * hd ** -0.5                       # [KV,g,T,T]
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = dot(pr, v.transpose(1, 0, 2)[:, None], mode)        # [KV,g,T,hd]
        o = o.transpose(2, 0, 1, 3).reshape(t, H * hd)
        x = x + dot(o, p["attn"]["wo"].reshape(H * hd, d), mode)
        h = rmsnorm(x, p["ln2"], eps)
        a = dot(h, p["mlp"]["w_gate"], mode)
        u = dot(h, p["mlp"]["w_up"], mode)
        x = x + dot(jax.nn.silu(a) * u, p["mlp"]["w_down"], mode)
        return x, (k, v)

    x = w["embed"][tokens]
    x, (ks, vs) = jax.lax.scan(layer, x, w["layers"])
    x = rmsnorm(x, w["final_norm"], eps)
    head = (w["embed"][:V].T if cfg["tie_word_embeddings"]
            else w["lm_head"][:, :V])
    return dot(x, head, mode), ks, vs


def forward(w: Dict, cfg: Dict, tokens, mode: str = "f32"
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Logits [T, vocab] over the real vocabulary, and every layer's keys
    and values after rotary embedding, [L, T, KV, hd] each, for one
    sequence.  The sequence is padded to a power of two (at least 256)
    so that a few programs serve every length; causal masking keeps the
    padding out of every real position."""
    n = len(tokens)
    t = max(256, 1 << (n - 1).bit_length())
    toks = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(tokens,
                                                             jnp.int32))
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rope_theta", "rms_norm_eps", "tie_word_embeddings")
    items = tuple((k, cfg[k]) for k in keys)
    logits, ks, vs = _forward(w, toks, items, mode)
    return logits[:n], ks[:, :n], vs[:, :n]
