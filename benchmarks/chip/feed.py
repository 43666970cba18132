"""Training batches from the seed, and the thread that prefetches them.

The generator is a copy of the program's ``repro.data.synthetic`` batch
draw, kept here so that the traffic cannot move with the program: each
batch is a pure function of (seed, step), every row differs, and the token
stream repeats an n-gram with random noise so that the loss is learnable.
A traffic file gives the parameters (``seq_len``, ``ngram``, ``noise``).
"""

from __future__ import annotations

import queue
import threading

import numpy as np


def batch_at(traffic: dict, vocab_size: int, seed: int, step: int,
             rows: int) -> dict:
    """{tokens, labels}: int32 arrays [rows, seq_len] for ``step``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    seq_len, ngram = traffic["seq_len"], traffic["ngram"]
    base = rng.integers(1, max(2, vocab_size // 4), size=(rows, ngram))
    reps = -(-seq_len // ngram) + 1
    seq = np.tile(base, (1, reps))[:, :seq_len + 1]
    noise = rng.random((rows, seq_len + 1)) < traffic["noise"]
    seq = np.where(noise, rng.integers(0, vocab_size, size=seq.shape), seq)
    return {"tokens": seq[:, :-1].astype(np.int32),
            "labels": seq[:, 1:].astype(np.int32)}


class Feed:
    """Batches for steps 0, 1, 2, ... made ahead on a thread of their own.

    ``next()`` returns the host arrays of the next step in order; the
    caller moves them to the device.  ``close()`` stops and joins the
    thread.
    """

    DEPTH = 2           # batches made ahead

    def __init__(self, traffic: dict, vocab_size: int, seed: int, rows: int):
        self._args = (traffic, vocab_size, seed)
        self._rows = rows
        self._q: queue.Queue = queue.Queue(maxsize=self.DEPTH)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self) -> None:
        step = 0
        while not self._stop.is_set():
            b = batch_at(*self._args, step, self._rows)
            while not self._stop.is_set():
                try:
                    self._q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def next(self) -> dict:
        return self._q.get(timeout=60.0)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():
            raise RuntimeError("the batch thread did not stop")
