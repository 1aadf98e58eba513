"""What family ``mla_lm`` makes of a serving traffic file and a seed: the
documents that set-up prefills, and the turns of a window. A turn is one
request: ``prompt_tokens`` new prompt ids, then a fixed count of generated
tokens (the request's size, ``size_quantiles``), greedy, no early stop.
Request ``i`` goes to session ``i mod sessions`` (round robin); session
``s`` starts from document ``s // sessions_per_document``. The arrival
gaps are the general generator's fixed multiset in an order drawn from
:data:`ARRIVAL_SEED`, so every turn is due at the same time whatever the
seed; the seed picks token ids, and nothing else.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from benchmarks.lib.traffic import arrivals, power_law_ids, rng_of

# The order of the arrival gaps is drawn from this and not from the run's
# seed, as weights.ROUTING_SEED draws the router: a step's time grows with
# the turns in flight, so the order decides how many turns overlap and the
# median turn with them, and with the order the seed's median moved by 10 %
# at 3 turns/s (PERF.md, section 6, PR 38).
ARRIVAL_SEED = 42


def sessions(traffic: dict) -> int:
    return int(traffic["documents"]) * int(traffic["sessions_per_document"])


def documents(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """``[documents, document_tokens]`` int32 ids by the power law."""
    return power_law_ids(rng_of(seed, 5), vocab,
                         (int(traffic["documents"]),
                          int(traffic["document_tokens"])),
                         float(traffic["id_alpha"]))


@dataclasses.dataclass
class SessionSchedule:
    """Every turn of a window: turn ``i`` is due ``due_s[i]`` seconds after
    the window opens, continues session ``session[i]`` with ``prompts[i]``
    and generates ``offsets[i+1] - offsets[i]`` tokens."""
    due_s: np.ndarray
    offsets: np.ndarray
    prompts: np.ndarray
    session: np.ndarray
    documents: np.ndarray
    per_document: int
    logits_at: Tuple[int, ...]

    def __len__(self):
        return len(self.due_s)

    def request(self, i: int):
        """``(prompt, session, tokens to generate, logits_at)``."""
        return (self.prompts[i], int(self.session[i]),
                int(self.offsets[i + 1] - self.offsets[i]), self.logits_at)

    def document_of(self, s: int) -> int:
        return s // self.per_document


def serve_schedule(traffic: dict, vocab: int, seed: int,
                   seconds: float) -> SessionSchedule:
    due, offsets = arrivals(traffic, rng_of(ARRIVAL_SEED, 2), seconds)
    n = len(due)
    prompts = power_law_ids(rng_of(seed, 2), vocab,
                            (n, int(traffic["prompt_tokens"])),
                            float(traffic["id_alpha"]))
    return SessionSchedule(
        due_s=due, offsets=offsets, prompts=prompts,
        session=np.arange(n) % sessions(traffic),
        documents=documents(traffic, vocab, seed),
        per_document=int(traffic["sessions_per_document"]),
        logits_at=tuple(int(j) for j in traffic["logits_at"]))
