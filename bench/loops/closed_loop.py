"""Closed loop: one caller, requests in rounds through the mix's variants.

Each round sends every variant once, in an order the seed permutes; the
caller waits for each answer before it sends the next request. Set-up
runs one whole round (every compiled shape loaded); the window then runs
rounds until ``seconds`` have passed and closes at the end of that round,
so every window holds the same balanced mix.

A mix whose optimiser takes a seed of its own (``request_seed``) gets one
per request, drawn from the run's seed and the request's place.
"""
from __future__ import annotations

import time
import traceback
from typing import Any, Dict, List

import numpy as np


def _seed_for(run_seed: int, stream: int, index: int) -> int:
    state = np.random.SeedSequence([run_seed, stream, index]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


def _rounds(traffic: dict, run_seed: int):
    rng = np.random.default_rng(np.random.SeedSequence([run_seed, 0]))
    n = len(traffic["variants"])
    while True:
        yield [int(i) for i in rng.permutation(n)]


def _kwargs(traffic: dict, run_seed: int, stream: int, index: int):
    kw = dict(traffic.get("kwargs", {}))
    key = traffic.get("request_seed")
    if key:
        kw[key] = _seed_for(run_seed, stream, index)
    return kw


def warm_up(program, traffic: dict, run_seed: int) -> None:
    """One round of every variant, with seeds of its own."""
    for i, v in enumerate(traffic["variants"]):
        program.request(v, _kwargs(traffic, run_seed, 1, i))


def window(program, traffic: dict, run_seed: int, seconds: float,
           annotate=None, on_round=None) -> Dict[str, Any]:
    """Run whole rounds until ``seconds`` have passed; return the answers
    (in order) and the window's wall seconds. ``annotate(name)`` wraps
    each request of the first round (a profiler annotation), and
    ``on_round(answers)`` runs after every round."""
    answers: List[Dict[str, Any]] = []
    failed = 0
    index = 0
    t0 = time.perf_counter()
    for rnd, order in enumerate(_rounds(traffic, run_seed)):
        for vi in order:
            variant = traffic["variants"][vi]
            kw = _kwargs(traffic, run_seed, 2, index)
            t = time.perf_counter()
            try:
                if annotate is not None and rnd == 0:
                    with annotate("bench.request"):
                        out = program.request(variant, kw)
                else:
                    out = program.request(variant, kw)
            except Exception as err:          # a request that never answers
                out = {"error": "".join(traceback.format_exception_only(
                    type(err), err)).strip()}
                failed += 1
            out.update(variant=vi, kwargs=kw, index=index,
                       wall_s=time.perf_counter() - t)
            answers.append(out)
            index += 1
        if on_round is not None:
            on_round(answers)
        if time.perf_counter() - t0 >= seconds:
            break
    return {"answers": answers, "failed": failed,
            "window_s": time.perf_counter() - t0}
