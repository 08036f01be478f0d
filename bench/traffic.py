"""The one traffic generator: a mix file's parameters and a seed in, a
schedule of requests out.

A mix is data (``bench/traffic/<mix>.json``), read by ``load_mix``; this
module turns it into requests. Every seed gets the same multiset of
prompt lengths, output lengths and inter-arrival gaps, each drawn at
evenly spaced quantiles of the mix's distributions; the seed only
permutes them and draws the token ids. So two seeds do the same work in
a different order, and the spread between runs is the system's, not the
draw's.

Mix keys (all lengths in tokens, times in seconds):

``loop``
    ``"open"``: requests are due on a schedule, whether or not earlier
    ones have finished (independent users). ``"closed"``: a fixed number
    of clients each send their next request when the last one finished.
``arrival`` (open loop)
    ``{"rate_rps": r}`` for Poisson arrivals, plus optionally
    ``"burst_rate_rps"``, ``"burst_s"`` and ``"period_s"`` for a rate
    that steps up to the burst rate for ``burst_s`` of every
    ``period_s`` (a Poisson process with that intensity, generated by
    rescaling time).
``clients_per_row`` (closed loop)
    Clients per resident-sequence row of the served engines.
``classes``
    A list of request classes, each ``{"weight", "prompt": {"median",
    "sigma", "min", "max"}, "output": {...}}``: lognormal lengths,
    clipped. Requests split between classes by weight.
``prefix`` (optional)
    ``{"groups": g, "share": s, "length": {...}}``: a share ``s`` of the
    requests begins with one of ``g`` shared prefixes (system prompts),
    drawn uniformly, of the given length, counted inside the prompt.
``containers`` (optional)
    Serving replicas behind the Router; by default one per chip.
``ramp_s``
    Seconds of load offered before the measured window opens, so that
    the window starts in steady state.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
# seconds past the window's close that an open-loop schedule keeps
# offering load, while the window's last requests get their first token
TAIL_S = 60.0


@dataclasses.dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    lo: int
    hi: int

    @classmethod
    def from_dict(cls, d: dict) -> "Lengths":
        return cls(float(d["median"]), float(d["sigma"]), int(d["min"]),
                   int(d["max"]))

    def quantiles(self, n: int) -> np.ndarray:
        """``n`` lengths at evenly spaced quantiles, clipped."""
        nd = NormalDist()
        z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
        v = np.exp(math.log(self.median) + self.sigma * z)
        return np.clip(np.rint(v), self.lo, self.hi).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str
    classes: tuple
    ramp_s: float
    rate_rps: float = 0.0
    burst_rate_rps: float = 0.0
    burst_s: float = 0.0
    period_s: float = 0.0
    clients_per_row: float = 0.0
    prefix: dict | None = None
    containers: int = 0     # serving replicas; 0: one per chip


def load_mix(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> Mix:
    path = directory / f"{name}.json"
    d = json.loads(path.read_text())
    loop = d["loop"]
    if loop not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    classes = tuple({"weight": float(c["weight"]),
                     "prompt": Lengths.from_dict(c["prompt"]),
                     "output": Lengths.from_dict(c["output"])}
                    for c in d["classes"])
    kw = {}
    if loop == "open":
        a = d["arrival"]
        kw = {"rate_rps": float(a["rate_rps"]),
              "burst_rate_rps": float(a.get("burst_rate_rps", 0.0)),
              "burst_s": float(a.get("burst_s", 0.0)),
              "period_s": float(a.get("period_s", 0.0))}
        if kw["rate_rps"] <= 0:
            raise ValueError(f"{path}: rate_rps must be > 0")
    else:
        kw = {"clients_per_row": float(d["clients_per_row"])}
    return Mix(name=name, loop=loop, classes=classes,
               ramp_s=float(d["ramp_s"]), prefix=d.get("prefix"),
               containers=int(d.get("containers", 0)), **kw)


@dataclasses.dataclass
class Req:
    rid: int
    due_s: float            # from the window's opening; < 0 in the ramp
    prompt: np.ndarray      # int32 token ids
    max_new: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def _lengths(mix: Mix, n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Prompt and output lengths of ``n`` requests: each class's share
    at evenly spaced quantiles, the whole set permuted by the seed."""
    w = np.array([c["weight"] for c in mix.classes], float)
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[np.argmax(w)] += n - counts.sum()
    plen = np.concatenate([c["prompt"].quantiles(k)
                           for c, k in zip(mix.classes, counts)])
    olen = np.concatenate([c["output"].quantiles(k)
                           for c, k in zip(mix.classes, counts)])
    # within a class, prompt and output quantiles pair up at random
    pair = _rng(seed, 1)
    for k, start in zip(counts, np.cumsum(np.r_[0, counts[:-1]])):
        olen[start:start + k] = pair.permutation(olen[start:start + k])
    order = _rng(seed, 2).permutation(n)
    return plen[order], olen[order]


def _intensity_inverse(mix: Mix, lam: np.ndarray) -> np.ndarray:
    """Map unit-rate arrival epochs to times under the mix's intensity:
    ``rate_rps``, stepped to ``burst_rate_rps`` for ``burst_s`` at the
    start of every ``period_s`` (time measured from the schedule's
    start)."""
    if mix.burst_s <= 0 or mix.period_s <= 0:
        return lam / mix.rate_rps
    per_period = (mix.burst_s * mix.burst_rate_rps
                  + (mix.period_s - mix.burst_s) * mix.rate_rps)
    k = np.floor(lam / per_period)
    rest = lam - k * per_period
    burst_mass = mix.burst_s * mix.burst_rate_rps
    t = np.where(rest < burst_mass, rest / mix.burst_rate_rps,
                 mix.burst_s + (rest - burst_mass) / mix.rate_rps)
    return k * mix.period_s + t


def mean_rate(mix: Mix) -> float:
    if mix.burst_s <= 0 or mix.period_s <= 0:
        return mix.rate_rps
    return (mix.burst_s * mix.burst_rate_rps
            + (mix.period_s - mix.burst_s) * mix.rate_rps) / mix.period_s


def schedule(mix: Mix, seed: int, window_s: float, vocab: int,
             n: int | None = None) -> list[Req]:
    """The requests of one run. Open loop: every request due from
    ``-ramp_s`` to ``TAIL_S`` past the window's close, at the mix's
    rate. Closed loop: ``n`` requests in the order clients take them
    (``due_s`` unused)."""
    if mix.loop == "open":
        span = mix.ramp_s + window_s + TAIL_S
        n = int(math.ceil(mean_rate(mix) * span)) + 1
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)   # Exp(1) quantiles
        gaps = _rng(seed, 3).permutation(gaps)
        due = _intensity_inverse(mix, np.cumsum(gaps)) - mix.ramp_s
    else:
        if n is None:
            raise ValueError("a closed loop needs a request count")
        due = np.zeros(n)
    plen, olen = _lengths(mix, n, seed)
    tok = _rng(seed, 4)
    prefixes: list[np.ndarray] = []
    share = 0.0
    if mix.prefix:
        p = mix.prefix
        pl_ = Lengths.from_dict(p["length"]).quantiles(int(p["groups"]))
        prefixes = [tok.integers(0, vocab, (int(k),), dtype=np.int32)
                    for k in _rng(seed, 5).permutation(pl_)]
        share = float(p["share"])
    pick = _rng(seed, 6)
    out = []
    for i in range(n):
        prompt = tok.integers(0, vocab, (int(plen[i]),), dtype=np.int32)
        if prefixes and pick.random() < share:
            pre = prefixes[int(pick.integers(len(prefixes)))]
            k = min(len(pre), len(prompt) - 1)
            prompt[:k] = pre[:k]
        out.append(Req(i, float(due[i]), prompt, int(olen[i])))
    return out
