"""One general traffic generator, driven by a data file of parameters.

The request list and its arrival schedule come from the traffic file alone
(its parameters and its own ``schedule_seed``): every run of a cell, whatever
its ``--seed``, holds the same requests with the same lengths, the same
sampler settings and the same due times in the same order. ``--seed`` sets
only what the work does not depend on: the token ids of each prompt and each
request's sampler seed (and, elsewhere, the weights).

Lengths are not drawn. For a list of N requests the generator takes the N
evenly spaced quantiles of the stated distribution, so the multiset of
lengths is exact, and ``schedule_seed`` only orders it.

Traffic file (JSON), all times in seconds:

    loop             "closed" (each client sends its next request when the
                     last completes) or "open" (requests are due on a schedule)
    clients_per_lane closed loop: clients = this x the configuration's lanes
    rate_rps         open loop: mean arrivals a second
    arrival          open loop: {"kind": "fixed_gap", "jitter": j}: gap 1/rate,
                     each arrival moved by up to +-j/2 gaps. A mix with
                     another kind of arrivals brings it with its traffic file
    requests         N, the length of the list; request k >= N repeats entry
                     k mod N with new token ids
    prompt_tokens,   {"dist": "lognormal", "median", "sigma", "min", "max"}
    max_tokens
    sampler          {"temperature", "top_p"}
    prompt_pattern   null, or {"kind": "repeat", "period": p}: a prompt's own
                     ids repeat with period p (its n-grams recur)
    start            {"in_flight": n or "lanes", "preroll_s": s}: the first n
                     requests are sent at once with their ``max_tokens`` cut to
                     evenly staggered remainders, as if already under way, and
                     the window opens s seconds later: the system is in the
                     schedule's steady state when measuring starts
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class RequestSpec:
    k: int                 # index in the order of issue
    prompt_tokens: int
    max_tokens: int        # as sent (cut for the requests "already under way")
    full_max_tokens: int   # the list's value
    temperature: float
    top_p: float
    due_s: float | None    # open loop: seconds after the load starts


def _quantile_lengths(spec: dict, n: int) -> list[int]:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    out = []
    for i in range(n):
        v = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(v)))))
    return out


class Traffic:
    """The request list and schedule of one traffic file at one lane count."""

    def __init__(self, params: dict, lanes: int):
        self.params = params
        self.lanes = lanes
        self.loop = params["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop is 'open' or 'closed', not {self.loop!r}")
        n = self.n = int(params["requests"])
        rng = np.random.default_rng(int(params["schedule_seed"]))
        prompts = _quantile_lengths(params["prompt_tokens"], n)
        outs = _quantile_lengths(params["max_tokens"], n)
        self._prompt_len = [prompts[i] for i in rng.permutation(n)]
        self._max_tokens = [outs[i] for i in rng.permutation(n)]
        self._sched_rng = rng  # arrivals continue the same stream
        self._due: list[float] = []
        start = params.get("start", {})
        in_flight = start.get("in_flight", 0)
        self.in_flight = lanes if in_flight == "lanes" else int(in_flight)
        self.preroll_s = float(start.get("preroll_s", 0.0))
        self.clients = (
            int(params["clients_per_lane"]) * lanes if self.loop == "closed"
            else 0
        )
        sampler = params.get("sampler", {})
        self.temperature = float(sampler.get("temperature", 0.0))
        self.top_p = float(sampler.get("top_p", 0.9))

    # -- schedule -------------------------------------------------------

    def _extend_due(self, upto: int) -> None:
        """Due times of the scheduled requests (those after the ones sent at
        once), made in order from the schedule's own stream."""
        rate = float(self.params["rate_rps"])
        arr = self.params.get("arrival", {"kind": "fixed_gap", "jitter": 0.0})
        if arr["kind"] != "fixed_gap":
            raise ValueError(f"unknown arrival kind {arr['kind']!r}")
        gap, jitter = 1.0 / rate, float(arr.get("jitter", 0.0))
        rng = self._sched_rng
        while len(self._due) <= upto:
            i = len(self._due)
            self._due.append((i + 1 + (rng.random() - 0.5) * jitter) * gap)

    def spec(self, k: int) -> RequestSpec:
        i = k % self.n
        full = self._max_tokens[i]
        sent = full
        if k < self.in_flight:
            # as if (k + 1/2) / in_flight of its answer were still to come
            sent = max(1, round(full * (k + 0.5) / self.in_flight))
        due = None
        if self.loop == "open":
            if k < self.in_flight:
                due = 0.0
            else:
                j = k - self.in_flight
                self._extend_due(j)
                due = self._due[j]
        return RequestSpec(
            k=k, prompt_tokens=self._prompt_len[i], max_tokens=sent,
            full_max_tokens=full, temperature=self.temperature,
            top_p=self.top_p, due_s=due,
        )

    # -- what --seed sets ------------------------------------------------

    def token_ids(self, seed: int, k: int, vocab_size: int) -> list[int]:
        """Request k's prompt: distinct random ids unless the file says that
        a prompt repeats itself. Ids 0 and 1 are left out (padding, BOS)."""
        n = self._prompt_len[k % self.n]
        own = np.random.default_rng([int(seed), 1, k])
        pattern = self.params.get("prompt_pattern")
        if pattern and pattern["kind"] == "repeat":
            period = int(pattern["period"])
            base = own.integers(2, vocab_size, size=min(period, n))
            ids = np.resize(base, n)
        else:
            ids = own.integers(2, vocab_size, size=n)
        return [int(x) for x in ids]

    @staticmethod
    def sampler_seed(seed: int, k: int) -> int:
        return int(np.random.default_rng([int(seed), 3, k]).integers(1, 2**31 - 1))

    def digest(self, n: int) -> str:
        """A fingerprint of the first n requests as the schedule fixes them
        (everything but token ids and sampler seeds): equal for every seed."""
        import hashlib

        h = hashlib.sha256()
        for k in range(n):
            s = self.spec(k)
            h.update(repr((s.k, s.prompt_tokens, s.max_tokens, s.temperature,
                           s.top_p, s.due_s)).encode())
        return h.hexdigest()[:16]
