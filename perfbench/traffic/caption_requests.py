"""Caption requests from a seed: the one generator every caption traffic file
(``traffic/<mix>.json`` with ``"generator": "caption_requests"``) is read by.

Parameters (all data, none in code):

    frames          frames per request, 0 for text only; fresh for every request
                    (the engine reuses vision features of a request object it
                    has seen, so nothing here is ever recycled)
    prefix_tokens   ids shared by every request, before the vision block: the
                    instruction every window of a job carries
    prompt_tokens   {"min", "max", "step"}: ids after the vision block, drawn
                    uniformly from the grid min, min+step, ..., max
    output_tokens   max_new_tokens of every request (greedy, min_tokens 0)
    backlog         requests kept waiting beyond the slots the mix can reach
    warm_rows       prefill programs are warmed for 1..warm_rows rows (powers of
                    two): how many prompts the steady loop can have in prefill at
                    once, since the slots' phases are spread
    trace_seconds   length of the traced slice of a --trace 1 run

Request ``i`` is a pure function of (parameters, seed, i): each has a
generator of its own, so requests can be made as the loop needs them.
``request(i, prompt_len=n)`` fixes the length (the driver's warm-up walks the
grid once, so that every shape the program's host-side operations are
specialised to has been met before the measured window opens).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class RequestSpec:
    """A request as plain data; the driver makes the program's own type."""

    request_id: str
    prefix_ids: list[int]
    prompt_ids: list[int]
    frames: np.ndarray | None  # uint8 [N, H, W, 3]
    max_new_tokens: int


def _ids(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    # the upper half of the vocabulary: clear of every tokenizer's specials
    return rng.integers(vocab // 2, vocab, n).tolist()


def _frames(rng: np.random.Generator, n: int, size: int) -> np.ndarray | None:
    if n == 0:
        return None
    shape = (n, size, size, 3)
    return np.frombuffer(rng.bytes(int(np.prod(shape))), np.uint8).reshape(shape)


class CaptionTraffic:
    def __init__(self, params: dict, seed: int, *, vocab: int, image_size: int) -> None:
        self.params = params
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.image_size = int(image_size)
        p = params["prompt_tokens"]
        self.grid = list(range(int(p["min"]), int(p["max"]) + 1, int(p["step"])))
        if not self.grid:
            raise ValueError(f"empty grid of prompt lengths: {p}")
        shared = np.random.default_rng([self.seed, 0])
        self.prefix_ids = _ids(shared, int(params["prefix_tokens"]), self.vocab)

    def request(
        self, i: int, *, name: str | None = None, prompt_len: int | None = None,
        max_new_tokens: int | None = None,
    ) -> RequestSpec:
        rng = np.random.default_rng([self.seed, 1, i])
        drawn = int(rng.choice(self.grid))
        return RequestSpec(
            request_id=name or f"w{i}",
            prefix_ids=list(self.prefix_ids),
            prompt_ids=_ids(rng, drawn if prompt_len is None else int(prompt_len), self.vocab),
            frames=_frames(rng, int(self.params["frames"]), self.image_size),
            max_new_tokens=int(self.params["output_tokens"]) if max_new_tokens is None else max_new_tokens,
        )

    def text_only(self, name: str, n_tokens: int) -> RequestSpec:
        """A seeded text-only request of exactly ``n_tokens`` prompt ids and no
        shared prefix: what the plain reference is compared on."""
        rng = np.random.default_rng([self.seed, 2, n_tokens])
        return RequestSpec(name, [], _ids(rng, n_tokens, self.vocab), None, 1)
