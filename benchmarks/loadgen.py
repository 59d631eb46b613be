"""One general traffic generator and one open-loop sender.

A traffic mix is a data file. The offered load does NOT depend on the
seed: the mix and ``--seconds`` fix how many requests there are, their gaps
and (prompt, answer) lengths, each taken at the quantile mid-points of its
distribution, and ONE order of them: a cycle as long as the window.
``--seed`` picks where the cycle is entered and draws the token ids. Every
run of a cell therefore offers the same requests, the same tokens, the
same mean rate and the same clusters of arrivals, in a rotated order. (A
free permutation was tried first, PR 24: the 90th percentile of TTFT then
differed by up to 2x between two seeds, because which requests happen to
arrive together decides the queue's tail.)
"""

from __future__ import annotations

import math
import queue
import random
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.common import generator

_NORMAL = statistics.NormalDist()


def quantile_midpoints(dist: Dict, n: int) -> List[float]:
    """``n`` values at the quantiles (i + 0.5) / n of ``dist``."""
    ps = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "fixed":
        xs = [float(dist["value"])] * n
    elif kind == "uniform":
        xs = [dist["lo"] + p * (dist["hi"] - dist["lo"]) for p in ps]
    elif kind == "lognormal":
        mu = math.log(dist["median"])
        xs = [math.exp(mu + dist["sigma"] * _NORMAL.inv_cdf(p)) for p in ps]
    elif kind == "exponential":
        xs = [-math.log(1.0 - p) for p in ps]  # mean ~1; scaled by caller
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "lo" in dist and kind != "uniform":
        xs = [min(max(x, dist["lo"]), dist["hi"]) for x in xs]
    return xs


def _lengths(dist: Dict, n: int) -> List[int]:
    return [int(round(x)) for x in quantile_midpoints(dist, n)]


def cycle(mix: Dict, span_s: float) -> List[Dict]:
    """The window's requests as ONE fixed cycle of ``span_s`` seconds:
    each entry has its (prompt, answer) lengths and ``gap``, the time to
    the next entry (the last one's runs to the first: the gaps sum to
    ``span_s``). The order is fixed (``random.Random(0)``); no seed
    changes it."""
    arrivals = mix["arrivals"]
    fixed = random.Random(0)
    if arrivals["dist"] == "bursts":
        n_bursts = int(span_s // arrivals["period_s"])
        k = arrivals["burst"]
        step = arrivals["within_s"] / max(1, k - 1)
        between = span_s / n_bursts - step * (k - 1)
        gaps = ([step] * (k - 1) + [between]) * n_bursts
    else:
        n = int(round(mix["rate_rps"] * span_s))
        gaps = quantile_midpoints(arrivals, n)
        scale = span_s / sum(gaps)  # the window is offered exactly n
        gaps = [g * scale for g in gaps]
        fixed.shuffle(gaps)
    n = len(gaps)
    prompts = _lengths(mix["prompt"], n)
    answers = _lengths(mix["answer"], n)
    fixed.shuffle(prompts)
    fixed.shuffle(answers)
    return [{"gap": g, "prompt_len": p, "n_new": a}
            for g, p, a in zip(gaps, prompts, answers)]


@generator("quantile_open_loop")
def quantile_open_loop(mix: Dict, seconds: float, seed: int,
                       vocab: int) -> List[Dict]:
    """The fixed cycle, entered at a place the seed picks (a burst's first
    request, where arrivals come in bursts): the window (due in
    [0, seconds), counted) is one whole turn of the cycle from there, the
    pre-roll (due < 0, not counted) the stretch of the cycle just before
    it. Token ids are drawn from the seed. Each request: due, prompt
    (int32 ids), prompt_len, n_new, counted."""
    rng = random.Random(seed)
    ids = np.random.default_rng(seed)
    cyc = cycle(mix, float(seconds))
    n = len(cyc)
    group = mix["arrivals"].get("burst", 1)
    k = rng.randrange(n // group) * group
    reqs, t = [], 0.0
    for j in range(n):
        c = cyc[(k + j) % n]
        reqs.append({"due": t, "counted": True, **c})
        t += c["gap"]
    pre, t, j = [], 0.0, 1
    while j <= n:
        c = cyc[(k - j) % n]
        t -= c["gap"]
        if t < -float(mix["preroll_s"]):
            break
        pre.append({"due": t, "counted": False, **c})
        j += 1
    reqs = pre[::-1] + reqs
    for r in reqs:
        r["prompt"] = ids.integers(0, vocab, r["prompt_len"], dtype=np.int32)
    return reqs


@generator("seeded_token_batches")
def seeded_token_batches(mix: Dict, seconds: float, seed: int, vocab: int):
    """An endless stream of fresh ``[global_batch, seq]`` batches of token
    ids for a trainer (targets are the tokens themselves, as in
    ``chip_smoke.py``; every position counts)."""
    rng = np.random.default_rng(seed)
    shape = (mix["global_batch"], mix["seq"])
    ones = np.ones(shape, np.float32)
    while True:
        toks = rng.integers(0, vocab, shape, dtype=np.int32)
        yield {"tokens": toks, "targets": toks, "mask": ones}


def offered(reqs: List[Dict]) -> Dict[str, int]:
    """What the window offers: the same for every seed of a cell."""
    win = [r for r in reqs if r["counted"]]
    return {
        "requests": len(win),
        "prompt_tokens": sum(r["prompt_len"] for r in win),
        "answer_tokens": sum(r["n_new"] for r in win),
        "preroll_requests": len(reqs) - len(win),
    }


class OpenLoop:
    """Sends each request at its due instant whether or not earlier ones
    have finished. One scheduler (the caller's thread) and a fixed pool of
    client threads, each holding one stream at a time; a request that
    finds no free client waits, and that shows as lateness."""

    def __init__(self, stream_fn: Callable, n_clients: int):
        self._stream_fn = stream_fn
        self._q: "queue.Queue[Optional[Dict]]" = queue.Queue()
        self._threads = [
            threading.Thread(target=self._client, daemon=True,
                             name=f"client-{i}")
            for i in range(n_clients)
        ]
        for t in self._threads:
            t.start()

    def _client(self):
        while True:
            r = self._q.get()
            if r is None:
                return
            r["times"], r["ids"] = [], []
            if r.get("cut"):  # the window ended while it waited for a client
                r["done"].set()
                continue
            try:
                r["sent"] = time.time()
                it = r["stream"] = self._stream_fn(r)
                for tok in it:
                    r["times"].append(time.time())
                    r["ids"].append(tok)
            except Exception as e:  # noqa: BLE001 — counted as a failure
                r["error"] = repr(e)
            finally:
                r["done"].set()

    def run(self, reqs: List[Dict], t0: float, seconds: float,
            drain_s: float, on_window_end: str,
            at_mid: Optional[Callable] = None,
            at_end: Optional[Callable] = None) -> None:
        """Blocks until the window's requests finished, were cut, or the
        drain limit passed. ``t0`` is the window's start (``time.time()``
        clock); dues are offsets from it."""
        for r in reqs:
            r["done"] = threading.Event()
        mid_done = at_mid is None
        for r in sorted(reqs, key=lambda r: r["due"]):
            if not mid_done and r["due"] >= seconds / 2:
                at_mid()
                mid_done = True
            wait = t0 + r["due"] - time.time()
            if wait > 0:
                time.sleep(wait)
            self._q.put(r)
        wait = t0 + seconds - time.time()
        if wait > 0:
            time.sleep(wait)
        if at_end is not None:
            at_end()
        counted = [r for r in reqs if r["counted"]]
        if on_window_end == "drain":
            deadline = time.time() + drain_s
            for r in counted:
                r["done"].wait(max(0.0, deadline - time.time()))
        for r in reqs:  # whatever is still open is cut here
            if not r["done"].is_set():
                r["cut"] = True
                stream = r.get("stream")
                if stream is not None:
                    stream.close()
        for r in reqs:
            r["done"].wait(10.0)

    def close(self):
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout=5.0)
