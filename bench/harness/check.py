"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed, is run through the
configuration's plain float32 reference: one causal pass over each prompt
followed by its served tokens.  At each served position the reference's
best logit minus its logit of the token that was served is the gap; a
greedy path that is right up to rounding serves the reference's best
token or one within rounding of it.  The number compared is the widest
gap over the sample.

The sample holds the request with the most served tokens, one that was
resumed from a decode snapshot and one that ran as several copies, where
such exist, and then requests drawn from the seed until it holds
``tokens`` served tokens or ``max_requests`` requests.

The control is the same reference computed in a lower precision
(``dtype``): at each position it takes the token that precision puts
first, and the gap of that token is read in the float32 logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed_rng


def weight_key(seed: int):
    """The weights' key for any non-negative seed (``jax.random.key``
    keeps only 32 bits of a larger one)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def sample(outputs: dict, *, seed: int, restored: set, replicated: set,
           tokens: int, max_requests: int) -> list[int]:
    done = sorted(outputs)
    if not done:
        return []
    rng = seed_rng(seed, 2)
    chosen = [max(done, key=lambda r: (len(outputs[r]), -r))]
    for pool in (restored, replicated):
        cands = [r for r in done if r in pool and r not in chosen]
        if cands:
            chosen.append(cands[int(rng.integers(len(cands)))])
    rest = [r for r in rng.permutation(done).tolist() if r not in chosen]
    while (rest and len(chosen) < max_requests
           and sum(len(outputs[r]) for r in chosen) < tokens):
        chosen.append(rest.pop(0))
    return chosen


class Reference:
    """The configuration's reference, weights made anew from the seed."""

    def __init__(self, ref_module, m: dict, seed: int, pad_len: int):
        self.pad_len = pad_len
        self.weights = jax.jit(lambda key: ref_module.init(key, m))(
            weight_key(seed))
        self._fwd = {
            dt: jax.jit(lambda w, t, _dt=dt: ref_module.forward(
                w, m, t, dtype=_dt))
            for dt in ("float32", "float8_e4m3fn")}

    def logits(self, prompt, served, dtype="float32") -> np.ndarray:
        """Logits at the positions that predict each served token."""
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        toks = np.zeros(self.pad_len, np.int32)
        toks[:seq.shape[0]] = seq
        out = self._fwd[dtype](self.weights, jnp.asarray(toks))
        p = len(prompt)
        return np.asarray(out[p - 1:p - 1 + len(served)])

    def free(self) -> None:
        self.weights = None


def gaps(logits: np.ndarray, tokens) -> np.ndarray:
    """Best logit minus the logit of each token, row by row."""
    tokens = np.asarray(tokens)
    rows = np.arange(tokens.shape[0])
    return logits.max(-1) - logits[rows, tokens]
