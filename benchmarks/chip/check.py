"""The comparison that decides ``correct``.

After the window, a sample of the finished requests drawn from the seed
(the one with the most served tokens always in it) is run through the
plain reference once, each prompt followed by the tokens the engine
served.  At the position that produced each served token, the gap is how
far that token's reference logit lies below the reference's best logit.
The engine decodes greedily, so a sound engine serves the reference's
best token up to rounding: most gaps are 0 and the rest are near-ties
that rounding flipped, while a wrong token has a gap of the order of the
logits' spread.  Four numbers summarise the sample: the widest gap, the
share of served tokens with a gap (a flip), and the mean gap and mean
squared gap over the served tokens.  Where the logits' error is e, a flip
needs a near-tie within e and its gap lies within e, so the share grows
as e, the mean gap as e^2 and the mean squared gap as e^3, while the
widest gap grows as e alone.  The configuration gives the limits of those
it compares.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from loadgen import rng_for

# served tokens the sample gathers at least, and requests at most
SAMPLE_TOKENS = 256
SAMPLE_REQUESTS = 8


def sample(finished: Sequence, seed: int, min_tokens: int = SAMPLE_TOKENS,
           max_requests: int = SAMPLE_REQUESTS) -> List:
    """The longest finished request, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), -r.index))
    rest = [r for r in finished if r is not longest]
    order = rng_for(seed, 2).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


@functools.partial(jax.jit, static_argnames=("fn", "model"))
def _gaps(params, seq, target, fn, model):
    lg = fn(params, dict(model), seq)
    best = lg.max(axis=-1)
    got = jnp.take_along_axis(lg, jnp.maximum(target, 0)[:, None],
                              axis=-1)[:, 0]
    return jnp.where(target >= 0, best - got, 0.0)


def served_gaps(reference, params, model: Dict, requests: Sequence,
                length: int) -> List[np.ndarray]:
    """The gap of every served token of each request under ``reference``.

    Sequences are padded to ``length`` (the engine's ``max_seq``), so one
    compiled program serves every request; padding after the last
    position does not change a causal forward at earlier positions."""
    frozen = tuple(sorted(model.items(), key=lambda kv: kv[0]))
    out = []
    for r in requests:
        served = list(r.tokens)
        seq = np.zeros((length,), np.int32)
        fed = list(r.prompt) + served[:-1]
        seq[:len(fed)] = fed
        target = np.full((length,), -1, np.int32)
        p = len(r.prompt)
        target[p - 1:p - 1 + len(served)] = served
        gaps = np.asarray(_gaps(params, jnp.asarray(seq), jnp.asarray(target),
                                reference.logits, frozen))
        out.append(gaps[p - 1:p - 1 + len(served)])
    return out


def numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    """max_gap, flip_share, mean_gap and mean_sq_gap over all served
    tokens."""
    allg = np.concatenate(gaps) if gaps else np.zeros((0,))
    if not allg.size:
        return {}
    return {"max_gap": float(allg.max()),
            "flip_share": float((allg > 0).mean()),
            "mean_gap": float(allg.mean()),
            "mean_sq_gap": float(np.square(allg).mean())}
