"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished, drawn from the seed and always holding
the one with the most served tokens, is teacher-forced through the plain
float32 reference (:mod:`bench.reference`).  At every position that
produced a served token, the gap by which that token's logit lies below
the reference's best is read; the widest gap over the sample is held to
the cell's limit (``bench/limits/<workload>.json``), which lies between
what sound runs of the program read and what the float8 control reads.
The run must also have served every request its full output, truncated
none at the cache bound, and compiled nothing inside the window.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
SAMPLE_TOKENS = 512
SAMPLE_MAX_REQUESTS = 8


def load_limits(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def sample(finished, seed: int) -> list:
    """The finished request with the most served tokens, then others in
    an order drawn from the seed, until SAMPLE_TOKENS served tokens or
    SAMPLE_MAX_REQUESTS requests."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: r.uid)
    longest = max(reqs, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in reqs if r is not longest]
    order = np.random.default_rng([seed % 2**63, 5]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def numbers(rec, gaps) -> list:
    """(name, value) of every number compared, before their limits."""
    short = sum(len(r.tokens) != rec.requests[r.uid].max_new
                for r in rec.finished)
    widest = max((float(np.max(g)) for g in gaps if len(g)),
                 default=float("inf"))
    return [("max_logit_gap", widest),
            ("short_outputs", float(short)),
            ("truncated", float(rec.truncated)),
            ("compiles_in_window", float(rec.compiles_in_window))]


def check(rec, limits: dict, reference_gaps) -> tuple:
    """(correct, [{"name", "value", "limit"}]): every number at or under
    its limit.  ``reference_gaps(seqs)`` returns the gaps of each
    (prompt, served tokens) pair."""
    picked = sample(rec.finished, rec.seed)
    gaps = reference_gaps([(r.prompt, r.tokens) for r in picked])
    rows = []
    for name, value in numbers(rec, gaps):
        lim = float(limits.get(name, 0.0))
        rows.append({"name": name, "value": value, "limit": lim})
    ok = bool(picked) and all(r["value"] <= r["limit"] for r in rows)
    served = sum(len(r.tokens) for r in picked)
    return ok, rows, len(picked), served
