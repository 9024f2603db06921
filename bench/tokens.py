"""The benchmark's own token generator: the synthetic stream the
training traffic is made of, from the seed alone.

Row r of step i's batch starts at a uniform token x_0 and follows
x_{t+1} = (31 x_t + n_t) mod V with n_t uniform in [0, 97), all drawn
from ``fold_in(PRNGKey(seed), i)``.  The system's data layer makes the
same stream (``repro.data.tokens.TokenStream``, whose arithmetic this
copies); the harness feeds the system through its data layer, so that
layer's cost is measured, and checks here that the tokens it trained on
are the traffic's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MULT = 31
NOISE = 97


def data_seed(seed: int) -> int:
    """The stream's seed: the run's seed folded into 32 bits."""
    return seed % 2**32


def batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    """Tokens (rows, seq) int32 of step ``step``."""
    key = jax.random.fold_in(jax.random.PRNGKey(data_seed(seed)), step)
    k1, k2, _ = jax.random.split(key, 3)
    x0 = jax.random.randint(k1, (rows,), 0, vocab, jnp.int32)
    noise = jax.random.randint(k2, (rows, seq), 0, NOISE, jnp.int32)

    def nxt(x, n):
        y = (x * MULT + n) % vocab
        return y, y

    _, toks = jax.lax.scan(nxt, x0, noise.T)
    return toks.T
