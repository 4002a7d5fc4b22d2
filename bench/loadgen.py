"""The one load generator: every traffic mix is a data file it reads.

A traffic file (``bench/traffic/<name>.json``) sets:

- ``loop``: ``"closed"`` (``outstanding`` requests kept in flight: the
  next is sent when one settles) or ``"open"`` (requests sent on a
  schedule whether or not earlier ones settled);
- ``arrivals`` for an open loop: ``"poisson"`` at ``rate_per_s``, or
  ``"onoff"``: bursts of ``on_s`` seconds and pauses of ``off_s``
  seconds at the same mean ``rate_per_s``;
- ``pool``: the JSONL(.gz) file of graph documents, relative to the
  checkout;
- ``popularity``: ``"uniform"`` (the pool in a fresh seeded order each
  pass, so every pool graph is sent equally often) or ``"zipf:<s>"``
  (graph of popularity rank ``r`` drawn with weight ``r**-s``);
- ``duplicate_share``: the share of requests that resend one of the
  last 64 graphs sent;
- ``serve``: ``ServeConfig`` overrides (``cache_size``, ``replicas``,
  ``max_wait_ms``, ...);
- ``warm_s``: seconds of this same traffic sent, untimed, in set-up.

The seed decides the order of the graphs and of the gaps between
arrivals. The set of gaps does not depend on it: an open loop's gaps are
fixed quantiles of its arrival distribution, shuffled by the seed and
scaled to end inside the window, so every seed sends the same amount of
work at the same mean rate.
"""
from __future__ import annotations

import gzip
import json
import math
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

#: Requests a duplicate may copy from.
DUPLICATE_WINDOW = 64


def load_pool(path: Path) -> List[str]:
    """The pool's documents as JSON lines (each request decodes its own
    fresh copy)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return [line for line in f.read().splitlines() if line.strip()]


def graph_size(line: str):
    """``(nodes, edges)`` of a pool document: unique edges without
    self-loops, as the served graph holds them."""
    doc = json.loads(line)
    edges = {(int(s), int(t)) for s, t in doc.get("edges", ()) if s != t}
    return len(doc["nodes"]), len(edges)


def draw_order(traffic: Dict, n_pool: int, rng: np.random.Generator,
               count: int) -> np.ndarray:
    """``count`` pool indices in send order."""
    pop = str(traffic.get("popularity", "uniform"))
    if pop == "uniform":
        passes = [rng.permutation(n_pool)
                  for _ in range(-(-count // n_pool))]
        order = np.concatenate(passes)[:count]
    elif pop.startswith("zipf:"):
        s = float(pop.split(":", 1)[1])
        ranked = rng.permutation(n_pool)
        w = np.arange(1, n_pool + 1, dtype=np.float64) ** -s
        order = ranked[rng.choice(n_pool, size=count, p=w / w.sum())]
    else:
        raise ValueError(f"unknown popularity {pop!r}")
    dup = float(traffic.get("duplicate_share", 0.0))
    if dup > 0:
        for i in range(1, count):
            if rng.random() < dup:
                order[i] = order[i - 1 - rng.integers(
                    min(i, DUPLICATE_WINDOW))]
    return order


def arrival_offsets(traffic: Dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Send times of an open loop, in seconds from the window's start,
    all inside ``[0, seconds)``."""
    rate = float(traffic["rate_per_s"])
    m = max(1, int(round(rate * seconds)))
    kind = traffic.get("arrivals", "poisson")
    if kind == "poisson":
        span, on, off = seconds, seconds, 0.0
    elif kind == "onoff":
        on, off = float(traffic["on_s"]), float(traffic["off_s"])
        span = seconds * on / (on + off)        # time spent in bursts
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    q = (np.arange(m) + 0.5) / m
    gaps = rng.permutation(-np.log1p(-q))       # exponential quantiles
    gaps *= span / gaps.sum()
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if off:                                     # burst time → wall time
        k = np.floor(t / on)
        t = k * (on + off) + (t - k * on)
    return t


class Requests:
    """Per-request timestamps (``time.perf_counter``) and outcomes."""

    def __init__(self) -> None:
        self.pool_idx: List[int] = []
        self.due: List[float] = []
        self.sent: List[float] = []
        self.submitted: List[float] = []
        self.done: List[float] = []
        self.futures: List = []
        self.errors: Dict[int, str] = {}

    def __len__(self) -> int:
        return len(self.due)

    def add(self, idx: int, due: float) -> int:
        """A new request (the generator's thread alone adds them)."""
        self.pool_idx.append(idx)
        self.due.append(due)
        self.sent.append(math.nan)
        self.submitted.append(math.nan)
        self.done.append(math.nan)
        self.futures.append(None)
        return len(self.due) - 1

    def settle(self, i: int, fut) -> None:
        self.done[i] = time.perf_counter()
        if fut is not None and fut.exception(0) is not None:
            self.errors[i] = repr(fut.exception(0))


def drive(traffic: Dict, pool: List[str], submit: Callable,
          rng: np.random.Generator, t0: float, seconds: float,
          rec: Requests, span: Callable) -> None:
    """Send the traffic from ``t0`` for ``seconds`` seconds.

    ``submit(doc)`` hands one decoded document to the system and returns
    its future; ``span(name)`` opens a host span for the trace. Each
    request's latency runs from when it was due, so a generator that
    falls behind shows as latency and as lag, never as a faster system.
    """
    t_end = t0 + seconds
    if traffic["loop"] == "closed":
        k = int(traffic["outstanding"])
        order = draw_order(traffic, len(pool), rng, 1 << 16)
        slots = threading.Semaphore(k)
        n = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            with span("bench.wait"):
                got = slots.acquire(timeout=t_end - now)
            if not got:
                return
            i = rec.add(int(order[n % len(order)]), time.perf_counter())
            n += 1
            _send(i, pool, submit, rec, span, slots.release)
    elif traffic["loop"] == "open":
        offsets = arrival_offsets(traffic, seconds, rng)
        order = draw_order(traffic, len(pool), rng, len(offsets))
        for off, idx in zip(offsets, order):
            due = t0 + off
            wait = due - time.perf_counter()
            if wait > 0:
                with span("bench.wait"):
                    time.sleep(wait)
            i = rec.add(int(idx), due)
            _send(i, pool, submit, rec, span, None)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")


def _send(i: int, pool: List[str], submit: Callable, rec: Requests,
          span: Callable, release: Optional[Callable]) -> None:
    def settled(fut):
        rec.settle(i, fut)
        if release is not None:
            release()

    doc = json.loads(pool[rec.pool_idx[i]])
    rec.sent[i] = time.perf_counter()
    try:
        with span("bench.submit"):
            fut = submit(doc)
    except Exception as e:                      # refused at the door
        rec.submitted[i] = time.perf_counter()
        rec.errors[i] = repr(e)
        rec.done[i] = rec.submitted[i]
        if release is not None:
            release()
        return
    rec.submitted[i] = time.perf_counter()
    rec.futures[i] = fut
    fut.add_done_callback(settled)
