"""End-to-end serving driver (the paper's kind of workload): batched
requests flow through the SynergAI scheduler onto engine replicas, and the
selected engine actually executes generation with a real model + KV cache.

It runs the reduced configs (2 layers, d_model 64, float32: the
``--reduced`` path of ``repro.launch.serve``) so it finishes on a CPU in
seconds; the scheduler still places each request against the published
engines' profiles.  The same engine at qwen3-4b's published widths runs on
a chip with ``python -m repro.launch.serve --arch qwen3-4b``.

    PYTHONPATH=src python examples/serve_cluster.py
"""

import time

import jax

from repro.core.estimator import candidate_order, estimate_matrix
from repro.core.offline import characterize
from repro.launch.serve import build_engine

# --- bring up two real engine replicas (reduced configs on CPU) -----------
ARCHS = {"qwen3-4b/bf16": "qwen3-4b", "rwkv6-1.6b/bf16": "rwkv6-1.6b"}
replicas = {engine_name: build_engine(arch, reduced=True, max_len=64)
            for engine_name, arch in ARCHS.items()}
print(f"brought up {len(replicas)} engine replicas")

# --- offline phase: the scheduler's view of the fleet ----------------------
cd = characterize()
workers = ["cloud-pod", "edge-large", "edge-small"]


# --- request loop: schedule with Eq. 1-4, execute on the replica ----------
class Request:
    def __init__(self, rid, engine, prompt_len, gen_len, t_qos):
        self.id, self.engine = rid, engine
        self.queries = 50
        self.t_qos = t_qos
        self.arrival = time.perf_counter()
        self.prompt_len, self.gen_len = prompt_len, gen_len


requests = [
    Request(0, "qwen3-4b/bf16", 16, 8, t_qos=30.0),
    Request(1, "rwkv6-1.6b/bf16", 16, 8, t_qos=30.0),
    Request(2, "qwen3-4b/bf16", 32, 8, t_qos=60.0),
]

key = jax.random.PRNGKey(7)
for req in requests:
    # SynergAI worker selection (Eq. 1-4) against the fleet model
    score = estimate_matrix(cd, [req], workers, now=0.0)
    order = candidate_order(score, 0)
    worker = workers[order[0]] if order else "cloud-pod"
    ent = cd.optimal(req.engine, worker)
    # execute on the local replica (stands in for the selected worker)
    eng = replicas[req.engine]
    toks = jax.random.randint(key, (2, req.prompt_len), 0,
                              eng.model.cfg.vocab)
    t0 = time.perf_counter()
    out = eng.generate({"tokens": toks}, req.gen_len)
    dt = time.perf_counter() - t0
    print(f"req {req.id} [{req.engine}] -> {worker} "
          f"(c*={ent.mode}/r{ent.chips_per_replica}); generated "
          f"{out.shape[1]} tokens x batch {out.shape[0]} in {dt:.2f}s")

s = replicas["qwen3-4b/bf16"].stats
print(f"\nqwen replica stats: prefill {s.prefill_tokens} tok, "
      f"decoded {s.decoded_tokens} tok over {s.batches} batches")
