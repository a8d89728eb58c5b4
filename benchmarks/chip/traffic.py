"""The one traffic generator: reads a mix file (``traffic/<mix>.json``)
and makes the cell's inputs from ``--seed``.

Every seed gets the same multiset of sizes and gaps, and only their order
and the token ids change with it: sizes and gaps are the quantiles
``(i + 0.5) / n`` of the mix's distributions, paired by a permutation that
is fixed for the mix, then shuffled by the seed.  A run's work is then the
same from seed to seed, and the spread between runs is the system's.

Serving mixes (``"path": "serve"``):

- ``arrival``: ``"poisson"``, open loop at ``rate_per_s``: exactly
  ``round(rate_per_s * seconds)`` requests are due in the window, at gaps
  drawn as exponential quantiles and scaled so the last is due inside it.
- ``prompt``: lengths from a lognormal (``median``, ``sigma``) snapped to
  the nearest rung of ``ladder`` in log space, so every prefill shape can
  be warmed up in set-up.
- ``output``: new tokens from a lognormal (``median``, ``sigma``) clipped
  to [``min``, ``max``].
"""

from __future__ import annotations

import json
import pathlib
from statistics import NormalDist

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
_FIXED = 0x5EED     # the pairing permutation: fixed for every seed


def load(name: str, root: pathlib.Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(u: np.ndarray, median: float, sigma: float) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    return median * np.exp(sigma * z)


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    ladder = np.asarray(spec["ladder"], np.float64)
    raw = _lognormal(_quantiles(n), spec["median"], spec["sigma"])
    idx = np.abs(np.log(raw)[:, None] - np.log(ladder)[None, :]).argmin(1)
    return ladder[idx].astype(np.int64)


def output_lengths(spec: dict, n: int) -> np.ndarray:
    raw = _lognormal(_quantiles(n), spec["median"], spec["sigma"])
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int):
    """The cell's requests in send order: dicts with ``due_s`` (seconds
    after the window opens), ``prompt`` (int32 tokens) and ``max_new``."""
    n = n_requests(mix, seconds)
    fixed = np.random.default_rng(_FIXED)
    prompts = prompt_lengths(mix["prompt"], n)
    outputs = output_lengths(mix["output"], n)[fixed.permutation(n)]
    rng = _rng(seed)
    order = rng.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    gaps = -np.log1p(-_quantiles(n))[rng.permutation(n)]
    t = np.cumsum(gaps)
    due = t * (seconds * n / (n + 1)) / t[-1]
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32),
             "max_new": int(outputs[i])} for i in range(n)]


def ladder(mix: dict):
    """The prompt lengths a mix can send: the shapes set-up warms."""
    return sorted({int(x) for x in mix["prompt"]["ladder"]})


def max_context(mix: dict) -> int:
    return max(ladder(mix)) + int(mix["output"]["max"])
