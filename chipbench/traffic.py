"""The one traffic generator.  A mix is a data file (``traffic/<mix>.json``)
of parameters; this module turns it and a seed into requests.

A session is a client's sequence of turns.  Every mix is sessions:

* open loop (``"loop": "open"``): sessions start at arrival times of a
  Poisson process at ``rate_per_s`` (optionally with ``bursts``);
* closed loop (``"loop": "closed"``): ``clients`` sessions start at once.
  Each client's first answer is cut to a phase drawn from the mix's
  stream, so the clients' requests end at spread times, as they would
  among clients long under way.

In both, turn k+1 is due ``think_s`` (exponential mean; 0 where the mix
gives none) after the last token of turn k's answer reached the client.

With ``system_prompts`` every session opens with one of a few shared
system prompts (Zipf popularity); with ``"history": true`` turn k's prompt
is the system prompt, each earlier turn with the answer the client
received for it, and the new turn.  Answers always run to their
``max_tokens`` (no mix has a stop token), so the served tokens change the
prompts' ids, not their lengths.  A session ends early where the next
prompt plus its answer would pass ``max_total`` tokens.

Sessions start from ``lead_s`` seconds before the run's first request, so
the run begins among sessions already under way.  Their turns before the
run are laid out ``lead_turn_s`` apart (exponential mean: a think time and
an answer's service time, as measured on the chip at the mix's rate), and
carry answers drawn from the seed; what they left in a deployment's prefix
cache is listed by :func:`primes`.  The first turn such a session sends in
the run is due where that layout puts it.

The sizes and the arrivals come from ``shape_seed`` in the mix and do not
depend on the run's seed, so every seed does the same work: the seed
draws the token ids (and the harness draws the weights from it).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Session:
    index: int
    first: int                         # the first turn sent in the run
    due_s: float                       # when turn ``first`` is due
    think_s: list[float]               # think time after each turn's answer
    system: int | None                 # shared system prompt id
    turns: list[tuple[int, int]]       # (user tokens, answer tokens)


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    v = np.round(spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n)))
    return np.clip(v, spec["min"], spec["max"]).astype(int)


def arrival_times(mix: dict, t0: float, t1: float, rate_per_s: float) -> np.ndarray:
    """Session start times in [t0, t1) from the mix's fixed stream.  With
    ``bursts`` the rate is ``factor`` times the base rate for ``length_s``
    of every ``every_s`` seconds (phase 0 at time 0), the base chosen so
    that the mean rate is ``rate_per_s``."""
    rng = np.random.default_rng([mix["shape_seed"], 1])
    b = mix.get("bursts")
    if b:
        frac = b["length_s"] / b["every_s"]
        base = rate_per_s / (1 - frac + frac * b["factor"])
        peak = base * b["factor"]
    else:
        base = peak = rate_per_s
    out, t = [], t0
    while True:                        # thinning of a rate-`peak` process
        t += rng.exponential(1.0 / peak)
        if t >= t1:
            return np.asarray(out)
        keep = rng.random()
        in_burst = bool(b) and (t % b["every_s"]) < b["length_s"]
        if in_burst or keep < base / peak:
            out.append(t)


def sessions(mix: dict, horizon_s: float,
             rate_per_s: float | None = None) -> list[Session]:
    """The sessions with a turn to send in [0, ``horizon_s``) (open loop),
    or the clients (closed loop, each due at 0)."""
    rate = mix.get("rate_per_s") if rate_per_s is None else rate_per_s
    if mix["loop"] == "open":
        starts = arrival_times(mix, -mix.get("lead_s", 0.0), horizon_s, rate)
    else:
        starts = np.zeros(mix["clients"])
    n = len(starts)
    shape = np.random.default_rng([mix["shape_seed"], 2])
    lo, hi = mix["turns"]["min"], mix["turns"]["max"]
    n_turns = shape.integers(lo, hi + 1, n)
    total = int(n_turns.sum())
    users = _lognormal(shape, mix["user_tokens"], total)
    answers = _lognormal(shape, mix["answer_tokens"], total)
    gaps = shape.exponential(1.0, total)   # scaled by think_s, lead_turn_s
    sp = mix.get("system_prompts")
    if sp:
        pop = np.arange(1, sp["count"] + 1, dtype=float) ** -sp["zipf_s"]
        system = shape.choice(sp["count"], n, p=pop / pop.sum())
    else:
        system = np.full(n, -1)
    bounds = np.concatenate([[0], np.cumsum(n_turns)])
    if mix["loop"] == "closed":
        phase = np.random.default_rng([mix["shape_seed"], 6]).random(n)
        heads = bounds[:-1]
        answers[heads] = np.maximum(1, np.ceil(phase * answers[heads]))
    out = []
    for j, start in enumerate(starts):
        a, b = bounds[j], bounds[j + 1]
        due = start + mix.get("lead_turn_s", 0.0) * np.concatenate(
            [[0.0], np.cumsum(gaps[a:b - 1])])
        first = int(np.searchsorted(due, 0.0))
        if first == b - a:
            continue                   # every turn came before the run
        out.append(Session(index=j, first=first, due_s=float(due[first]),
                           think_s=(mix.get("think_s", 0.0) * gaps[a:b]).tolist(),
                           system=None if system[j] < 0 else int(system[j]),
                           turns=list(zip(users[a:b].tolist(),
                                          answers[a:b].tolist()))))
    return out


class Tokens:
    """Token ids of a run: shared system prompts, each session's turns and
    the answers of its turns before the run, drawn from the seed below
    ``vocab``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        sp = mix.get("system_prompts")
        rng = np.random.default_rng([seed, 3])
        self.system = ([] if not sp else
                       [rng.integers(0, vocab, sp["tokens"]).tolist()
                        for _ in range(sp["count"])])

    def turn(self, session: int, turn: int, n: int, part: int = 0) -> list[int]:
        """``part`` 0: the user's turn; 1: a turn's answer before the run."""
        return np.random.default_rng([self.seed, 4, session, turn, part]).integers(
            0, self.vocab, n).tolist()


def prompt_for(mix: dict, toks: Tokens, s: Session, k: int,
               served: dict[int, list[int]] | None = None) -> list[int] | None:
    """Turn k's prompt, or None when it and its answer would not fit
    ``max_total`` (the session ends there).  ``served`` maps a turn sent in
    the run to the answer the client received; the turns before the run
    carry the seed's."""
    head = toks.system[s.system] if s.system is not None else []
    body = []
    if mix.get("history"):
        for j in range(k):
            body += toks.turn(s.index, j, s.turns[j][0])
            body += (served[j] if j >= s.first else
                     toks.turn(s.index, j, s.turns[j][1], part=1))
    prompt = head + body + toks.turn(s.index, k, s.turns[k][0])
    if len(prompt) + s.turns[k][1] > mix["max_total"]:
        return None
    return prompt


def primes(mix: dict, toks: Tokens, sess: list[Session]) -> list[list[list[int]]]:
    """What the turns before the run left in the prefix cache, in two
    rounds: each shared system prompt; then, for every session under way,
    its last turn before the run: that turn's prompt followed by its
    answer."""
    out: list[list[int]] = []
    if not mix.get("history"):
        return [toks.system, out]
    for s in sess:
        if s.first > 0:
            k = s.first - 1
            p = prompt_for(mix, toks, s, k)
            if p is not None and prompt_for(mix, toks, s, s.first) is not None:
                out.append(p + toks.turn(s.index, k, s.turns[k][1], part=1))
    return [toks.system, out]
