"""One generator for every traffic mix: a mix is a JSON file of
parameters under ``bench/traffic/<name>.json``.

Every seed gets the same work in another order: prompt and output
lengths are the quantiles of the mix's clipped lognormals at fixed
points, and the gaps between open-loop arrivals are the quantiles of an
exponential at the mix's rate.  The k-th request takes the quantile at
index (a + k g) mod N, with g near N times the golden ratio's fraction
and the offset a drawn from ``--seed``: over all N requests that is each
quantile once, and any run of consecutive requests spreads evenly over
the distribution, so a window of a few tens of requests holds about the
same work whatever the seed.  The open-loop arrival gaps are ordered the
same way, so their exponential spread is kept but their bursts are
evened out; the seed also draws the token ids.

Closed loop (``"loop": "closed"``): ``clients`` callers (``"slots"``:
one per engine slot), each waiting for its reply before it sends the
next request.  The first request of
each client gets an output length drawn from the mix's residual-life
distribution, so retirements start spread out as in a long-running
service instead of in lock step.

Open loop (``"loop": "open"``): Poisson arrivals at ``rate`` requests per
second, due on a schedule whatever the server does.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
N_QUANTILES = 4096


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""
    uid: int
    prompt: list
    max_new: int
    client: int = -1
    due: float = 0.0     # seconds after the schedule's start (open loop)


def load_mix(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    mix["name"] = name
    return mix


def length_quantiles(spec: dict, n: int = N_QUANTILES) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, clipped to [min, max] and rounded."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def residual_life_quantiles(lengths: np.ndarray, n: int) -> np.ndarray:
    """n values at the quantiles (i + 0.5) / n of the residual life of a
    renewal process whose lifetimes are ``lengths``: P(R = r) is
    proportional to the number of lifetimes of r or more."""
    top = int(lengths.max())
    counts = np.bincount(lengths, minlength=top + 1)
    at_least = np.cumsum(counts[::-1])[::-1][1:]          # r = 1 .. top
    cdf = np.cumsum(at_least) / at_least.sum()
    q = (np.arange(n) + 0.5) / n
    return np.searchsorted(cdf, q) + 1


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, *salt])


def _stride(n: int, step: float) -> int:
    """A stride near n * step that is coprime to n."""
    g = max(1, int(round(n * step)))
    while math.gcd(g, n) != 1:
        g += 1
    return g


def even_order(values: np.ndarray, offset: int, step: float) -> np.ndarray:
    """``values`` (sorted quantiles) reordered so that entry k is
    values[(offset + k g) mod n]: a permutation whose consecutive runs
    spread evenly over the sorted values."""
    n = len(values)
    return values[(offset + np.arange(n) * _stride(n, step)) % n]


class Generator:
    """Requests of one mix for one seed and vocabulary."""

    def __init__(self, mix: dict, seed: int, vocab: int, slots: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        c = mix.get("clients")
        self.clients = slots if c == "slots" else c
        a, b = _rng(seed, 1).integers(0, N_QUANTILES, 2)
        # two irrational steps, so that prompt and output lengths do not
        # move together
        self.prompt_lens = even_order(length_quantiles(mix["prompt"]), a,
                                      2 ** 0.5 - 1)
        self.output_lens = even_order(length_quantiles(mix["output"]), b,
                                      (5 ** 0.5 - 1) / 2)
        self.next_uid = 0

    def _tokens(self, uid: int, n: int) -> list:
        return _rng(self.seed, 2, uid).integers(0, self.vocab, n).tolist()

    def _item(self, max_new: int | None = None, **kw) -> Item:
        uid = self.next_uid
        self.next_uid += 1
        i = uid % N_QUANTILES
        n_prompt = int(self.prompt_lens[i])
        out = int(self.output_lens[i]) if max_new is None else max_new
        return Item(uid=uid, prompt=self._tokens(uid, n_prompt),
                    max_new=out, **kw)

    # -- closed loop --------------------------------------------------------
    def first_requests(self) -> list:
        """One request per client, outputs from the residual life."""
        n = self.clients
        res = _rng(self.seed, 3).permutation(
            residual_life_quantiles(length_quantiles(self.mix["output"]), n))
        return [self._item(max_new=int(r), client=c)
                for c, r in enumerate(res)]

    def next_request(self, client: int) -> Item:
        return self._item(client=client)

    # -- open loop ----------------------------------------------------------
    def schedule(self, seconds: float) -> list:
        """Requests due within ``seconds`` of the schedule's start."""
        rate = self.mix["rate"]
        n = max(1, math.ceil(rate * seconds * 1.25) + 16)
        q = (np.arange(n) + 0.5) / n
        gaps = even_order(-np.log1p(-q) / rate,
                          int(_rng(self.seed, 4).integers(0, n)),
                          (3 ** 0.5 - 1) / 2)
        out, t = [], 0.0
        for g in gaps:
            t += float(g)
            if t > seconds:
                break
            out.append(self._item(due=t))
        return out
