"""The one traffic generator.  A mix is a data file of parameters under
``traffic/``; every request's text, seed and due time is drawn from
``--seed``, so the same seed gives the same inputs.

Mix keys:

  loop                "closed" | "open"
  clients             closed loop: requests kept in flight (default 1)
  arrival_seed        open loop: the seed of the Poisson arrival times.
                      They are the mix's, the same in every run, so that
                      every run offers the same amount of work at the same
                      instants; texts and seeds come from --seed
  rate_rps            open loop: arrivals per second, one number per
                      configuration name ({"<config>": rate}); a mix serves
                      a configuration it has a rate for
  text_words          words per prompt text (default 12)
  tail_percentile     the percentile beyond which a tail metric reads
                      (80 | 90 | 95)
"""

from __future__ import annotations

import copy
import math
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))
WORDS_FILE = os.path.join(os.path.dirname(HERE), "traffic", "words.txt")
_SEED_SPAN = 2 ** 48


def load_words() -> list[str]:
    with open(WORDS_FILE, encoding="utf-8") as f:
        words = [w.strip() for w in f if w.strip()]
    if len(words) < 64:
        raise ValueError(f"{WORDS_FILE}: {len(words)} words, want >= 64")
    return words


class Traffic:
    """Draws requests.  ``next_request()`` gives the next (text, seed);
    texts never repeat, so the program's exact-hit result cache and its
    coalescer see what the cell names and nothing else."""

    def __init__(self, mix: dict, config_name: str, seed: int):
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"mix loop {self.loop!r}: closed or open")
        self.clients = int(mix.get("clients", 1))
        self.text_words = int(mix.get("text_words", 12))
        if self.loop == "open":
            rates = mix["rate_rps"]
            if config_name not in rates:
                raise ValueError(
                    f"mix has no rate for configuration {config_name!r} "
                    f"(it has {sorted(rates)}): add a mix file")
            self.rate = float(rates[config_name])
            if self.rate <= 0:
                raise ValueError("rate_rps must be above 0")
            self._arrivals = random.Random(int(mix["arrival_seed"]))
        self._rng = random.Random(int(seed))
        self._words = load_words()
        self._seen: set[str] = set()
        self._n = 0

    def _text(self) -> str:
        while True:
            text = " ".join(self._rng.choice(self._words)
                            for _ in range(self.text_words))
            if text not in self._seen:
                self._seen.add(text)
                return text

    def next_request(self) -> dict:
        """One request: a text of its own and a seed."""
        req = {"index": self._n, "text": self._text(),
               "seed": self._rng.randrange(_SEED_SPAN)}
        self._n += 1
        return req

    def schedule(self, seconds: float) -> list[dict]:
        """Open loop: every request due inside ``[0, seconds)``, each
        with its ``due`` time, in order.  Poisson arrivals: exponential
        gaps at the configuration's rate, drawn from ``arrival_seed``."""
        if self.loop != "open":
            raise ValueError("only an open loop has a schedule")
        out, t = [], 0.0
        while True:
            t += -math.log(1.0 - self._arrivals.random()) / self.rate
            if t >= seconds:
                return out
            out.append(self.next_request() | {"due": t})


def fill_graph(config: dict, req: dict, prefix: str) -> dict:
    """The configuration's graph with this request's text, seed and
    file-name prefix written into the inputs the configuration names
    under ``vary``, and each input under ``rotate`` set to the value whose
    turn it is (request ``index`` modulo the number of values)."""
    graph = copy.deepcopy(config["graph"])
    for key, value in (("text", req["text"]), ("seed", req["seed"]),
                       ("filename_prefix", prefix)):
        for node, field in config["vary"][key]:
            graph[node]["inputs"][field] = value
    for rot in config.get("rotate", []):
        value = rot["values"][req["index"] % len(rot["values"])]
        for node, field in rot["inputs"]:
            graph[node]["inputs"][field] = value
    return graph


def rotation_length(config: dict) -> int:
    """Requests after which every rotated value has had its turn."""
    return math.lcm(*(len(r["values"]) for r in config.get("rotate", [])))
