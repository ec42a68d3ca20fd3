"""Open-loop agent-session traffic, made from a traffic file and a seed.

A session arrives at a Poisson time, and each of its turns fans out one
request to every decode module of the deployment. A module's prompt is the
shared system prompt, the session's document, the tool observations of the
turns so far, and that module's own instruction tail. A turn is due when
every reply of the turn before it has finished. Replies are not fed back.

Every seed gets the same work at the same times: the number of sessions in
a stream, their sizes and their arrival times come from the traffic file's
``shape_seed`` alone, and the run seed draws the token ids. A window holds
only a few sessions of this engine's capacity, so letting the seed reorder
them moved the tails by tens of percent from seed to seed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: seed streams: the warm-up and the post-window traffic never share token
#: ids or sizes with the measured window
STREAMS = {"system": 0, "warmup": 1, "window": 2, "post": 3}
FIRST_TOKEN_ID = 4          # ids below are left to the engine's specials


@dataclass
class Session:
    sid: str
    arrival: float                   # seconds after its stream opens
    document: list
    observations: list               # one token list per turn after the first
    tails: dict                      # module -> its instruction tail
    replies: list                    # replies[turn][module] -> max tokens

    @property
    def turns(self) -> int:
        return len(self.replies)

    def prompt(self, system: list, turn: int, module: str) -> list:
        ctx = list(system) + self.document
        for obs in self.observations[:turn]:
            ctx += obs
        return ctx + self.tails[module]


def _rng(*key) -> np.random.Generator:
    return np.random.default_rng([int(k) % (1 << 63) for k in key])


def _length(rng, bounds, quantum: int) -> int:
    lo, hi = bounds
    n = int(rng.integers(lo, hi + 1))
    return max(quantum, n // quantum * quantum)


def _reply(rng, bounds, dist: str) -> int:
    lo, hi = bounds
    if dist == "log_uniform":
        return int(round(np.exp(rng.uniform(np.log(lo), np.log(hi)))))
    return int(rng.integers(lo, hi + 1))


def system_prompt(traffic: dict, vocab: int, seed: int) -> list:
    rng = _rng(seed, STREAMS["system"])
    return rng.integers(FIRST_TOKEN_ID, vocab,
                        traffic["system_prompt_tokens"]).tolist()


def _shapes(traffic: dict, modules: list, count: int, stream: str):
    """The stream's fixed session sizes and arrival gaps."""
    rng = _rng(traffic["shape_seed"], STREAMS[stream])
    q = int(traffic.get("length_quantum", 1))
    gaps = rng.exponential(1.0, count + 1)
    sizes = []
    for _ in range(count):
        turns = int(traffic["turns"])
        sizes.append({
            "document": _length(rng, traffic["document_tokens"], q),
            "observations": [_length(rng, traffic["observation_tokens"], q)
                             for _ in range(turns - 1)],
            "tails": {m: _length(rng, traffic["tail_tokens"], q)
                      for m in modules},
            "replies": [{m: _reply(rng, traffic["reply_tokens"],
                                   traffic.get("reply_distribution",
                                               "uniform"))
                         for m in modules} for _ in range(turns)],
        })
    return gaps, sizes


def sessions(traffic: dict, modules: list, vocab: int, seed: int,
             stream: str, seconds: float) -> list:
    """The sessions that arrive in ``seconds`` of ``stream``, ordered by
    arrival. Their count is the traffic's rate times ``seconds``; given the
    count, Poisson arrival times are uniform order statistics, drawn here as
    normalised exponential gaps."""
    count = max(1, int(round(traffic["rate_sessions_per_s"] * seconds)))
    gaps, sizes = _shapes(traffic, modules, count, stream)
    arrivals = seconds * np.cumsum(gaps)[:-1] / gaps.sum()
    tok = _rng(seed, STREAMS[stream], 2)

    def draw(n):
        return tok.integers(FIRST_TOKEN_ID, vocab, n).tolist()

    out = []
    for i, (t, sz) in enumerate(zip(arrivals, sizes)):
        out.append(Session(
            sid=f"{stream}{i}", arrival=float(t),
            document=draw(sz["document"]),
            observations=[draw(n) for n in sz["observations"]],
            tails={m: draw(n) for m, n in sz["tails"].items()},
            replies=sz["replies"]))
    return out
