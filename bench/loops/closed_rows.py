"""The closed loop of ``closed.py`` (one client; the same end-to-end
metrics) for requests of many rows served by a mixture-of-experts
engine.

The ring is served in one order of sizes for every seed: the prompt and
output lengths, in their order, of the ring the mix's generator draws
for the mix's ``order_seed``, each filled with a prompt of that length
drawn from the run's seed.  Requests of many rows take seconds each, so
a window holds only a few; in the seed's own order which few would
change from run to run, and the tokens per second with them.  The seed
still draws every token, and so the routing.

``sample`` compares rows, not whole requests: the longest request and
``sample_requests - 1`` more drawn from the seed, ``sample_rows`` rows of
each drawn from the seed (dropless routing keeps a request's rows
independent, so a row can be checked alone).  ``counters`` adds the
engine's expert counters over the window (``expert_load``: routed
assignments to held experts and held experts touched, by phase) and, in
a traced run, the named scopes of the decode program's instructions
(``scopes``), which the scope readers map the trace's operations by.
"""

from __future__ import annotations

import glob
import importlib
import os
import tempfile
import time

import numpy as np

from bench.loops import closed
from bench.requests import Request
from bench.span_reduce import RUN_TRACE_DIR

NEEDS = closed.NEEDS + ("expert_load", "scope_map")
EDGE_S = 0.005


def traced_since(t: float) -> bool:
    """Whether ``bench/run.py`` wrote a trace, in its trace directory,
    of a window that began at or after ``t`` (wall clock)."""
    return any(os.path.getmtime(f) >= t for f in glob.glob(os.path.join(
        tempfile.gettempdir(), RUN_TRACE_DIR + "*", "**", "*.xplane.pb"),
        recursive=True))


def in_fixed_order(requests, traffic: dict) -> list:
    """``requests`` (the seed's ring) with the sizes, in the order, of the
    ring that the mix's generator draws for ``order_seed``: each prompt
    length's prompts, in the seed's order, fill that length's places."""
    req = traffic["requests"]
    gen = importlib.import_module("bench.requests." + req["kind"])
    order = gen.make(req, int(traffic["order_seed"]), 2)
    by_len = {}
    for r in requests:
        by_len.setdefault(r.prompt_len, []).append(r.prompts)
    if sorted(r.prompt_len for r in order) != sorted(
            r.prompt_len for r in requests):
        raise ValueError("the seed's ring and the fixed order hold "
                         "different prompt lengths")
    return [Request(by_len[r.prompt_len].pop(0), r.out) for r in order]


class Loop(closed.Loop):
    def __init__(self, system, requests, traffic: dict, seed: int, emit):
        super().__init__(system, in_fixed_order(requests, traffic),
                         traffic, seed, emit)

    def window(self, seconds: float):
        self.t_window = time.time()
        self.load = self.system.expert_load()
        # a traced window holds one request, and the trace's clocks agree
        # to a millisecond or so: start its prefill program well inside
        # the window's span (before the measured window's clock starts)
        time.sleep(EDGE_S)
        super().window(seconds)
        after = self.system.expert_load()
        self.load = {k: {ph: after[k][ph] - v for ph, v in d.items()}
                     for k, d in self.load.items()}

    def counters(self) -> dict:
        out = super().counters()
        out["expert_load"] = self.load
        if traced_since(self.t_window):
            out["scopes"] = self.system.scope_map()
        return out

    def sample(self):
        rng = np.random.default_rng([self.seed, 6])
        reqs = [self.requests[k] for k, _ in self.served]
        size = np.array([r.prompt_len + r.out for r in reqs])
        pick = [int(rng.choice(np.flatnonzero(size == size.max())))]
        rest = [int(k) for k in rng.permutation(len(reqs))
                if k != pick[0]]
        pick += rest[:int(self.traffic["sample_requests"]) - 1]
        out = []
        for k in pick:
            rows = np.sort(rng.choice(reqs[k].batch,
                                      int(self.traffic["sample_rows"]),
                                      replace=False))
            out.append((Request(reqs[k].prompts[rows], reqs[k].out),
                        self.served[k][1][rows]))
        return out
