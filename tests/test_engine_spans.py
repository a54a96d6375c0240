"""The engine's and the estimator's profiler spans, read back from a trace
recorded on the CPU around two requests of the reduced qwen3-4b engine and
one placement estimate."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.core.estimator import estimate_matrix
from repro.core.job import Job
from repro.models.registry import build_model
from repro.serving.engine import InferenceEngine

# (rows, prompt length, tokens) of each request
REQUESTS = [(2, 16, 5), (1, 8, 3)]
WORKERS = ["cloud-pod", "edge-large", "edge-small"]


def _prompts(vocab):
    return [jax.random.randint(jax.random.PRNGKey(10 + k), (b, s), 0, vocab)
            for k, (b, s, _) in enumerate(REQUESTS)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory, configdict):
    """(tokens without a trace, tokens under it, host events by line)."""
    from jax.profiler import ProfileData

    cfg = reduced(get_config("qwen3-4b"))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab)
    plain = InferenceEngine(model, params, max_len=32)
    untraced = [np.asarray(plain.generate({"tokens": p}, n))
                for p, (_, _, n) in zip(prompts, REQUESTS)]

    eng = InferenceEngine(model, params, max_len=32)
    jobs = [Job(k, "qwen3-4b/bf16", queries=100, t_qos=120.0, arrival=0.0)
            for k in range(3)]
    logdir = str(tmp_path_factory.mktemp("spans"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        estimate_matrix(configdict, jobs, WORKERS, now=0.0)
        traced = [np.asarray(eng.generate({"tokens": p}, n))
                  for p, (_, _, n) in zip(prompts, REQUESTS)]
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(ev.name, ev.start_ns, ev.end_ns,
                               dict(ev.stats)) for ev in line.events
                              if ev.name.startswith(("engine.",
                                                     "placement."))])
    return untraced, traced, [ln for ln in lines if ln]


def _named(lines, name):
    return [(k, ev) for k, ln in enumerate(lines) for ev in ln
            if ev[0] == name]


def _inside(lines, k, outer, name):
    """The events ``name`` on line ``k`` that lie within ``outer``."""
    return [ev for ev in lines[k] if ev[0] == name
            and outer[1] <= ev[1] and ev[2] <= outer[2]]


def test_one_generate_span_per_request(recorded):
    _, _, lines = recorded
    gens = sorted(_named(lines, "engine.generate"), key=lambda g: g[1][1])
    assert [(g[3]["rows"], g[3]["prompt_len"], g[3]["tokens"])
            for _, g in gens] == REQUESTS
    assert [g[3]["request"] for _, g in gens] == [1, 2]


def test_steps_and_samples_nest_in_their_request(recorded):
    _, _, lines = recorded
    gens = sorted(_named(lines, "engine.generate"), key=lambda g: g[1][1])
    for (k, g), (_, _, n) in zip(gens, REQUESTS):
        steps = _inside(lines, k, g, "engine.decode_step")
        samples = _inside(lines, k, g, "engine.sample")
        assert sorted(s[3]["step"] for s in steps) == list(range(1, n))
        assert sorted(s[3]["step"] for s in samples) == list(range(n))
        for s in samples:
            if s[3]["step"]:
                (step,) = [d for d in steps if d[1] <= s[1]
                           and s[2] <= d[2]]
                assert step[3]["step"] == s[3]["step"]
        assert len(_inside(lines, k, g, "engine.prefill")) == 1
        assert len(_inside(lines, k, g, "engine.wait")) == 1
    for name in ("engine.decode_step", "engine.sample", "engine.prefill",
                 "engine.wait"):
        assert len(_named(lines, name)) == sum(
            len(_inside(lines, k, g, name)) for k, g in gens)


def test_placement_span(recorded):
    _, _, lines = recorded
    (_, ev), = _named(lines, "placement.estimate")
    assert ev[3] == {"jobs": 3, "workers": len(WORKERS)}


def test_tokens_unchanged_by_the_trace(recorded):
    untraced, traced, _ = recorded
    for a, b, (rows, _, n) in zip(untraced, traced, REQUESTS):
        assert a.shape == (rows, n)
        np.testing.assert_array_equal(a, b)
