"""Open-loop traffic from a mix file and a seed.

A traffic mix is a JSON file under ``bench/traffic/`` that this one
generator reads:

    {"prompt_len": 128, "mask_rate": 0.15,
     "flag_cycle": [{}, {"size": 1.0}, {"size": 8.0}, {"recency": 2.0}],
     "priority_mix": [0.9, 0.08, 0.02], "min_confidence": 0.0,
     "phases": [{"seconds": 1.0, "rate_rps": 900.0},
                {"seconds": 5.0, "rate_rps": 36.0}]}

Phases repeat in order from the window's start; a single phase with
``"seconds": 0`` spans the whole window.  Each phase instance holds
``round(rate * seconds)`` arrivals whose gaps are the quantiles of an
exponential distribution, shuffled by the seed: every seed offers the
same number of requests with the same set of gaps, in another order, so
the seed changes which prompt arrives when and not how much work a run
holds.  The priorities (``priority_mix``) and the domains (a uniform
mixture over the corpus's eight) are fixed counts, shuffled the same
way, and the flags cycle with the request index.

Prompts come from a copy of the repo's synthetic corpus
(``repro.data.corpus.DomainCorpus``, order-1 Markov chains per domain
over a 512-id vocabulary) with the repo's BERT-style 15% masking
(``repro.data.batching.mlm_batch``), and the flag cycle and priority
mix of ``repro.launch.serve.serve_library``.  ``OpenLoop`` emits each
request once it is due and leaves its ``arrival`` unset, so the engine
stamps it on receipt as it does behind the repo's own entry
(``launch/serve.py``); the due times stay in ``Traffic.due``, and
latency is measured from them, so a stall in the serve loop still shows.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Callable

import numpy as np

VOCAB = 512
MASK = 1
N_SPECIAL = 4
DOMAINS = ("github", "uspto", "pubmed", "freelaw", "dm_math",
           "stackexchange", "books", "commoncrawl")
# per-domain (branching factor, private-vocabulary weight, motif)
_PROFILE = {
    "github": (4, 0.75, "brackets"), "uspto": (8, 0.70, None),
    "pubmed": (8, 0.70, None), "freelaw": (10, 0.60, None),
    "dm_math": (3, 0.80, "digits"), "stackexchange": (6, 0.55, "brackets"),
    "books": (14, 0.45, None), "commoncrawl": (20, 0.30, None),
}


class Corpus:
    """Order-1 Markov chain per domain over a shared vocabulary, with a
    private high-frequency sub-vocabulary per domain and structural
    motifs (bracket pairs for code, digit runs for math)."""

    def __init__(self, seed: int, shared_frac: float = 0.35):
        rng = np.random.default_rng(seed)
        usable = np.arange(N_SPECIAL, VOCAB)
        n_shared = int(len(usable) * shared_frac)
        self.shared = usable[:n_shared]
        self.private = dict(zip(DOMAINS,
                                np.array_split(usable[n_shared:],
                                               len(DOMAINS))))
        self.tables = {}
        for d in DOMAINS:
            branch, priv_w, _ = _PROFILE[d]
            drng = np.random.default_rng(rng.integers(0, 2**31))
            n_priv = max(1, int(round(branch * priv_w)))
            succ = np.concatenate(
                [drng.choice(self.private[d], size=(VOCAB, n_priv)),
                 drng.choice(self.shared, size=(VOCAB, branch - n_priv))],
                axis=1).astype(np.int32)
            w = 1.0 / np.arange(1, branch + 1) ** 1.2
            self.tables[d] = (succ, w / w.sum())

    def sample(self, domain: str, n: int, seq: int,
               rng: np.random.Generator) -> np.ndarray:
        succ, w = self.tables[domain]
        out = np.empty((n, seq), np.int32)
        cur = rng.choice(self.private[domain], size=n)
        out[:, 0] = cur
        steps = rng.choice(len(w), size=(n, seq), p=w)
        for s in range(1, seq):
            cur = succ[cur, steps[:, s]]
            out[:, s] = cur
        motif = _PROFILE[domain][2]
        if motif == "brackets":
            opn, cls = self.shared[0], self.shared[1]
            for b in range(n):
                for _ in range(rng.integers(1, max(2, seq // 16))):
                    i = rng.integers(0, seq - 3)
                    j = rng.integers(i + 2, min(seq, i + 12))
                    out[b, i], out[b, j] = opn, cls
        elif motif == "digits":
            digits = self.shared[2:12]
            for b in range(n):
                i = rng.integers(0, seq - 8)
                run = rng.integers(4, 8)
                out[b, i:i + run] = rng.choice(digits, size=run)
        return out


def mlm_mask(tokens: np.ndarray, rng: np.random.Generator,
             rate: float) -> tuple[np.ndarray, np.ndarray]:
    """BERT-style masking (80% [MASK], 10% random id, 10% kept; never
    position 0).  Returns (inputs, mask) with mask int32."""
    mask = rng.random(tokens.shape) < rate
    mask[:, 0] = False
    inputs = tokens.copy()
    r = rng.random(tokens.shape)
    inputs[mask & (r < 0.8)] = MASK
    rand = mask & (r >= 0.8) & (r < 0.9)
    inputs[rand] = rng.integers(N_SPECIAL, VOCAB, size=int(rand.sum()))
    return inputs, mask.astype(np.int32)


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("prompt_len", "mask_rate", "flag_cycle", "priority_mix",
                "min_confidence", "phases"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    if not mix["phases"] or any(p["rate_rps"] <= 0 or p["seconds"] < 0
                                for p in mix["phases"]):
        raise ValueError(f"{path}: phases need rate_rps > 0 and "
                         f"seconds >= 0")
    if len(mix["phases"]) > 1 and any(p["seconds"] == 0
                                      for p in mix["phases"]):
        raise ValueError(f"{path}: only a single phase may span the "
                         f"window (seconds 0)")
    return mix


def exp_gaps(n: int, span: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps that sum to ``span``: the exponential distribution's
    quantiles at (k + 1/2) / n, in the seed's order."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    g *= span / g.sum()
    return rng.permutation(g)


def due_offsets(mix: dict, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
    """Due times of every arrival in the window, in seconds from its
    start, ascending."""
    phases = mix["phases"]
    out, t = [], 0.0
    while t < seconds:
        for p in phases:
            span = p["seconds"] or seconds
            n = int(round(p["rate_rps"] * span))
            if n:
                gaps = exp_gaps(n, span, rng)
                out.append(t + np.concatenate([[0.0], np.cumsum(gaps[:-1])]))
            t += span
            if t >= seconds:
                break
    due = np.concatenate(out) if out else np.zeros(0)
    return due[due < seconds]


def fixed_counts(weights, n: int, rng: np.random.Generator) -> np.ndarray:
    """Labels 0..k-1 in the proportions of ``weights``, exactly ``n`` of
    them (largest remainders), shuffled."""
    w = np.asarray(weights, float) / np.sum(weights)
    counts = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return rng.permutation(np.repeat(np.arange(len(w)), counts))


@dataclasses.dataclass
class Traffic:
    """The requests of one run with their due offsets (seconds from the
    window's start)."""
    requests: list
    due: np.ndarray


def make_traffic(mix: dict, seconds: float, seed: int) -> Traffic:
    """Every request due in a window of ``seconds``, from ``seed``."""
    from repro.serving.requests import Request

    rng = np.random.default_rng(seed)
    corpus = Corpus(seed)
    due = due_offsets(mix, seconds, rng)
    n, seq = len(due), int(mix["prompt_len"])
    domain = fixed_counts(np.ones(len(DOMAINS)), n, rng)
    tokens = np.empty((n, seq), np.int32)
    for di, d in enumerate(DOMAINS):
        rows = np.flatnonzero(domain == di)
        if len(rows):
            tokens[rows] = corpus.sample(d, len(rows), seq, rng)
    inputs, mask = mlm_mask(tokens, rng, float(mix["mask_rate"]))
    prio = fixed_counts(mix["priority_mix"], n, rng)
    flags = mix["flag_cycle"]
    reqs = [Request(uid=i, tokens=inputs[i], targets=tokens[i],
                    mask=mask[i], lambdas=dict(flags[i % len(flags)]),
                    priority=int(prio[i]),
                    min_confidence=float(mix["min_confidence"]))
            for i in range(n)]
    return Traffic(reqs, due)


class OpenLoop:
    """Feed ``requests`` to ``TryageEngine.serve`` on their schedule.

    Each request is emitted at or after its due time ``t0 + due`` on
    the engine's clock, with ``arrival`` left for the engine to stamp on
    receipt; while nothing is due the feed yields ``None`` idle ticks
    (sleeping at most 1 ms between them) so the scheduler can fire
    deadline flushes.  ``on_close`` runs once, at the
    first tick at or after the window's close ``t0 + seconds``; the
    feed ends once the window has closed and every request is out (a
    backlog left at the close is still emitted, late).  ``lateness``
    collects emission time minus due time per request; ``wait_span``
    makes the context each sleep runs in (a trace annotation in a traced
    run)."""

    def __init__(self, traffic: Traffic, t0: float, seconds: float,
                 on_close: Callable[[], None] = lambda: None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 wait_span=contextlib.nullcontext):
        self.reqs = traffic.requests
        self.due = t0 + np.asarray(traffic.due, float)
        self.end = t0 + seconds
        self.on_close = on_close
        self.clock, self.sleep = clock, sleep
        self.wait_span = wait_span
        self.lateness: list[float] = []

    def __iter__(self):
        i, n = 0, len(self.reqs)
        closed = False
        while True:
            now = self.clock()
            if not closed and now >= self.end:
                closed = True
                self.on_close()
            if i < n and self.due[i] <= now:
                self.lateness.append(now - self.due[i])
                yield self.reqs[i]
                i += 1
                continue
            if i >= n and closed:
                return
            yield None
            nxt = self.due[i] if i < n else self.end
            wait = nxt - self.clock()
            if wait > 0:
                with self.wait_span():
                    self.sleep(min(wait, 1e-3))
