"""Random paged-attention inputs shared by the fp and int8 kernel tests."""
import jax
import jax.numpy as jnp
import numpy as np


def paged_attn_inputs(b, t, h, kv, d, n, bs, maxb, seed=0, layout="ragged"):
    """Random pools and queries over a block table of ``layout``:
    ``ragged`` tables, each row's length within its table; ``spare``: every
    row holds as many blocks as it may, its length at most two blocks;
    ``full``: long rows that fill most of their table; ``overflow``: as
    ``full``, but row 0 holds all MAXB blocks and its queries lie past
    MAXB*BS."""
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    pool_k = jax.random.normal(ks[1], (n, bs, kv, d))
    pool_v = jax.random.normal(ks[2], (n, bs, kv, d))
    table = np.full((b, maxb), -1, np.int32)
    kvp = np.full((n, bs), -1, np.int32)
    qpos = np.zeros((b, t), np.int32)
    perm = rng.permutation(n)
    c = 0
    for i in range(b):
        # leave >= 1 pool block per remaining row
        avail = min(maxb, n - c - (b - 1 - i))
        nb = (rng.randint(1, max(avail, 1) + 1) if layout == "ragged"
              else avail)
        table[i, :nb] = perm[c:c + nb]
        c += nb
        if layout == "spare":
            ntok = rng.randint(t, 2 * bs + 1)
        elif layout == "overflow" and i == 0:
            assert nb == maxb
            ntok = maxb * bs + t + 3
        elif layout == "ragged":
            # clamp so short tables stay valid (a query past the
            # allocated blocks just attends a partial history)
            ntok = rng.randint(t, max(nb * bs, t) + 1)
        else:
            ntok = rng.randint(max(t, (nb - 1) * bs), nb * bs + 1)
        for p in range(min(ntok, nb * bs)):
            kvp[table[i, p // bs], p % bs] = p
        qpos[i] = np.arange(ntok - t, ntok)
    return (q, pool_k, pool_v, jnp.asarray(table), jnp.asarray(qpos),
            jnp.asarray(kvp))
