"""One general generator for every traffic mix.

A mix is a data file under ``traffic/``, parameters only; a role calls
the generator below for the kind of input it feeds (``kind`` in the file
says which, for the reader).  The same ``--seed`` gives the same inputs.
The program receives only what is generated here.

A serving mix is a *fixed trace, replayed*: its requests' sizes and the
gaps between them are drawn once, from the mix's own ``population_seed``,
and every ``--seed`` replays that one cyclic trace from another phase
(see ``request_schedule``).  ``--seed`` decides the phase, every token,
and the weights.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                *salt]))


# ---------------------------------------------------------------------------
# training: token batches
# ---------------------------------------------------------------------------

def token_sequences(mix: Dict[str, Any], vocab: int, seed: int,
                    n_sequences: int) -> np.ndarray:
    """``(n, seq_len + 1)`` int32 1-based token ids that follow
    ``token[t+1] = (a * token[t] + c) mod vocab``: the next token is a
    function of the last, the same function in every sequence, so a model
    can learn it and the loss falls.  ``a``, ``c`` and each sequence's
    start come from the seed."""
    rng = _rng(seed, 11)
    a = int(rng.choice([3, 5, 7, 11, 13]))
    c = int(rng.integers(1, vocab))
    t = int(mix["seq_len"]) + 1
    out = np.empty((n_sequences, t), np.int64)
    out[:, 0] = rng.integers(0, vocab, n_sequences)
    for i in range(1, t):
        out[:, i] = (a * out[:, i - 1] + c) % vocab
    return (out + 1).astype(np.int32)


# ---------------------------------------------------------------------------
# training: image batches (made on the device)
# ---------------------------------------------------------------------------

def image_batches(mix: Dict[str, Any], image: int, channels: int,
                  classes: int, key):
    """``n_batches`` resident batches, made on the device in one jitted
    call: ``(x (n, B, C, H, W) float32, y (n, B) int32 1-based)``.  The
    label is a function of the image: each class adds its own pattern of
    +-1 on a coarse 4 x 4 grid per channel to unit noise."""
    import jax
    import jax.numpy as jnp
    n, b = int(mix["n_batches"]), int(mix["batch"])
    grid = 4
    cell = image // grid

    @jax.jit
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        y = jax.random.randint(k1, (n, b), 0, classes)
        patterns = jnp.sign(jax.random.normal(
            k2, (classes, channels, grid, grid)))
        coarse = patterns[y]                       # (n, b, C, 4, 4)
        fine = jnp.repeat(jnp.repeat(coarse, cell, axis=-2), cell, axis=-1)
        pad = image - grid * cell
        fine = jnp.pad(fine, [(0, 0)] * 3 + [(0, pad), (0, pad)])
        x = jax.random.normal(k3, (n, b, channels, image, image)) + fine
        return x.astype(jnp.float32), (y + 1).astype(jnp.int32)

    return make(key)


# ---------------------------------------------------------------------------
# serving: an open loop of requests
# ---------------------------------------------------------------------------

def _lognormal(rng, median: float, sigma: float, lo: int, hi: int,
               n: int) -> np.ndarray:
    x = np.exp(rng.normal(np.log(median), sigma, n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def request_schedule(mix: Dict[str, Any], seed: int, seconds: float,
                     vocab: int) -> List[Dict[str, Any]]:
    """Requests due over ``lead_in_s + seconds``: ``[{due_s, prompt (int32
    1-based ids), max_new}]``, sorted by due time.  The window is
    ``[lead_in_s, lead_in_s + seconds)``.

    A fixed trace, replayed.  The trace is ``round(rate * period_s)``
    requests: prompt length, answer length and the gap to the next
    arrival, each drawn from the mix's ``population_seed`` (gaps
    exponential, as Poisson arrivals have them, then scaled so that the
    trace fills ``period_s`` exactly).  It is cyclic: after the last
    request comes the first.  Nothing in it depends on ``--seconds`` or
    on ``--seed``.  A run replays it from a phase drawn from ``--seed``
    for ``lead_in_s + seconds``; with ``seconds == period_s`` (the
    benchmark's ``run_seconds``) the window holds every request of the
    trace exactly once and opens on the state it closes on, whatever the
    seed.  So every seed offers the same work with the same neighbours,
    in another place of the window; what the seed changes besides is
    every prompt's tokens.  The three draws have a stream each, so the
    trace at a lower rate is a prefix of the trace at a higher one (the
    sweep that finds the knee changes only ``rate``)."""
    rate, lead_in = float(mix["rate"]), float(mix["lead_in_s"])
    period = float(mix["period_s"])
    n = max(1, int(round(rate * period)))
    pop = int(mix["population_seed"])
    p, a = mix["prompt_tokens"], mix["answer_tokens"]
    prompts = _lognormal(_rng(pop, 21), p["median"], p["sigma"], p["min"],
                         p["max"], n)
    answers = _lognormal(_rng(pop, 22), a["median"], a["sigma"], a["min"],
                         a["max"], n)
    answers = np.minimum(answers, int(mix["max_total_tokens"]) - prompts)
    gaps = _rng(pop, 23).exponential(1.0, n)
    gaps *= period / gaps.sum()
    at = (np.cumsum(gaps) - gaps[0] + _rng(seed, 24).random() * period) \
        % period
    tokens = _rng(seed, 25)
    out = []
    # cycle k of the trace covers [k * period, (k + 1) * period) of the
    # window's clock; the lead-in is the end of the cycle(s) before 0
    k = -int(np.ceil(lead_in / period))
    while k * period < seconds:
        for t, plen, alen in zip(at + k * period, prompts, answers):
            if -lead_in <= t < seconds:
                out.append({"due_s": float(t + lead_in),
                            "prompt": tokens.integers(
                                1, vocab + 1, int(plen), dtype=np.int32),
                            "max_new": int(alen)})
        k += 1
    out.sort(key=lambda r: r["due_s"])
    return out
