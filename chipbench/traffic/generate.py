"""One generator for every traffic mix: a mix is a JSON file of
parameters beside this module, ``<mix>.json``.

Built so that every seed offers the same work, in the same order: the
schedule is drawn from a fixed stream and the seed draws only the token
ids, so which call queues behind which is the same in every run.

- the arrival count is fixed: ``round(rate_per_s * seconds)`` calls fall
  due in the window, at uniform times, sorted (a Poisson process
  conditioned on its count);
- lengths are stratified: each distribution is read at the fixed
  quantiles ``(k + 1/2) / n``, shuffled once;
- contexts are balanced: each gets ``n / contexts`` calls (the first
  ``n mod contexts`` one more), shuffled once (the paper's ``random``
  pattern, stratified).

Token ids are uniform over the vocabulary, from the seed.  Prompt lengths of the
``table3`` distribution follow ``repro.trace.synth``: the paper's Table
3 ranges are per-call deltas, uniform within each dataset's range, of
which ``prompt_share`` is the prompt (the rest is the reference answer,
whose place the drawn output length takes).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent

# the paper's Table 3: dataset -> per-call delta range in tokens
TABLE3 = {
    "agnews": (200, 500),
    "xsum": (1000, 2000),
    "samsum": (100, 300),
    "cnn_dailymail": (500, 1000),
    "wmt17_de_en": (100, 500),
    "sst2": (10, 100),
}


def load(mix: str) -> dict:
    return json.loads((HERE / f"{mix}.json").read_text())


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The fixed multiset of ``n`` lengths of a distribution spec, in
    quantile order."""
    u = quantiles(n)
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, float(spec["n"]))
    elif dist == "uniform":
        lo, hi = int(spec["lo"]), int(spec["hi"])
        out = lo + np.floor(u * (hi - lo + 1))
    elif dist == "lognormal":
        nd = NormalDist(np.log(float(spec["median"])), float(spec["sigma"]))
        out = np.exp([nd.inv_cdf(x) for x in u])
        out = np.clip(np.round(out), int(spec["lo"]), int(spec["hi"]))
    elif dist == "table3":
        # equal weight per dataset; the k-th quantile falls in dataset
        # floor(u * D), uniform within its range
        names = list(spec["datasets"])
        D = len(names)
        j = np.minimum((u * D).astype(int), D - 1)
        w = u * D - j
        lo = np.array([TABLE3[names[i]][0] for i in j], float)
        hi = np.array([TABLE3[names[i]][1] for i in j], float)
        out = lo + np.floor(w * (hi - lo + 1))
        out = np.maximum(1, np.floor(out * float(spec.get("prompt_share",
                                                          1.0))))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return out.astype(np.int64)


@dataclass
class Call:
    idx: int
    due: float                  # seconds after the window opens
    ctx: int
    prompt: np.ndarray          # int32 token ids
    max_new: int


@dataclass
class Schedule:
    n_contexts: int
    histories: List[np.ndarray]
    calls: List[Call]
    warm: List[Call] = field(default_factory=list)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def build(mix: dict, seconds: float, seed: int, vocab: int,
          rate_per_s: float = None) -> Schedule:
    """The calls due in a window of ``seconds``, the contexts' histories,
    and the warm-up calls.  Their times, contexts and lengths are the
    same for every seed; their token ids come from ``seed``."""
    rate = float(mix["rate_per_s"] if rate_per_s is None else rate_per_s)
    C = int(mix["contexts"])
    n = max(1, int(round(rate * seconds)))
    r_time, r_len, r_ctx = (_rng(0, i) for i in range(3))
    r_tok = _rng(seed, 3)

    due = np.sort(r_time.uniform(0.0, seconds, n))
    p_len = r_len.permutation(lengths(mix["prompt"], n))
    o_len = r_len.permutation(lengths(mix["output"], n))
    h_len = r_len.permutation(lengths(mix["history"], C))
    ctx = r_ctx.permutation(np.arange(n) % C)

    def toks(k):
        return r_tok.integers(1, vocab, size=int(k)).astype(np.int32)

    histories = [toks(h) for h in h_len]
    calls = [Call(i, float(due[i]), int(ctx[i]), toks(p_len[i]),
                  int(o_len[i])) for i in range(n)]
    return Schedule(C, histories, calls, warm_calls(mix, n, r_tok, vocab))


def warm_calls(mix: dict, n: int, rng: np.random.Generator,
               vocab: int) -> List[Call]:
    """Calls that, before the window, reach every prompt length bucket
    the window's calls can use and every decode batch size: one call per
    power-of-two prompt bucket in the mix's range, then ``decode_batch``
    calls on distinct contexts with output lengths 2, 3, ... so that the
    batch shrinks through every size."""
    C = int(mix["contexts"])
    sv = mix["service"]
    cs = int(sv.get("chunk_tokens", 16))
    pl = lengths(mix["prompt"], n)
    sizes = sorted({_bucket(int(x), cs) for x in pl})
    out: List[Call] = []
    for i, b in enumerate(sizes):
        k = min(b, int(pl.max()))
        out.append(Call(-1, 0.0, i % C, rng.integers(
            1, vocab, size=k).astype(np.int32), 1))
    B = int(sv["decode_batch"])
    for j in range(B):
        out.append(Call(-1, 0.0, (len(sizes) + j) % C, rng.integers(
            1, vocab, size=cs).astype(np.int32), 2 + j))
    return out


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def history_parts(history: np.ndarray, limit: int) -> List[np.ndarray]:
    """A history in prompt-only calls of at most ``limit`` tokens."""
    return [history[i:i + limit] for i in range(0, len(history), limit)]


def describe(s: Schedule) -> Dict[str, float]:
    p = [len(c.prompt) for c in s.calls]
    o = [c.max_new for c in s.calls]
    return {"calls": len(s.calls), "contexts": s.n_contexts,
            "prompt_tokens": int(np.sum(p)), "output_tokens": int(np.sum(o)),
            "history_tokens": int(sum(len(h) for h in s.histories))}
