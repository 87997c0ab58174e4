"""Serving engine + gang executor integration tests."""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ParallelConfig
from repro.core.executor import BEJob, GangExecutor, RTJob
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model
from repro.obs import flight
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = reduced(get_config("qwen2-7b"))
    mesh = make_local_mesh(1, 1)
    api = build_model(cfg, ParallelConfig(param_dtype="float32",
                                          compute_dtype="float32",
                                          q_block=8, kv_block=8), mesh)
    params = api.init(jax.random.key(0))
    return cfg, api, params


def test_engine_matches_stepwise_greedy(tiny_lm):
    """Engine generation == naive greedy rollout via repeated prefill."""
    cfg, api, params = tiny_lm
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, size=(12,)).astype(np.int32)
    n_new = 5

    engine = ServingEngine(api, params, max_batch=2, max_seq=64)
    req = Request(rid=0, prompt=prompt, max_new=n_new)
    engine.run_until_done([req], max_steps=50)
    assert req.done and len(req.out) == n_new

    # oracle: repeated full prefill argmax
    toks = list(prompt)
    oracle = []
    for _ in range(n_new):
        logits, _ = jax.jit(api.prefill_fn)(
            params, {"tokens": jnp.asarray([toks], jnp.int32)})
        nxt = int(jnp.argmax(logits[0, -1]))
        oracle.append(nxt)
        toks.append(nxt)
    assert req.out == oracle, (req.out, oracle)


def test_engine_concurrent_slots(tiny_lm):
    cfg, api, params = tiny_lm
    rng = np.random.default_rng(2)
    engine = ServingEngine(api, params, max_batch=2, max_seq=64)
    reqs = [Request(rid=i, prompt=rng.integers(
        1, cfg.vocab_size, size=(8,)).astype(np.int32), max_new=4)
        for i in range(4)]
    engine.run_until_done(reqs, max_steps=100)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 4 for r in reqs)


def test_executor_one_gang_at_a_time():
    """Two RT jobs at different priorities never hold lanes concurrently."""
    ex = GangExecutor(n_lanes=4, regulation_interval_s=0.01)
    overlap = []

    running = set()

    def mk_fn(name, dur):
        def fn(lane, idx):
            running.add(name)
            if len({n for n in running}) > 1:
                overlap.append(tuple(running))
            time.sleep(dur)
            running.discard(name)
        return fn

    ex.submit_rt(RTJob("hi", mk_fn("hi", 0.002), lanes=(0, 1), prio=9,
                       period_s=0.02, n_jobs=20))
    ex.submit_rt(RTJob("lo", mk_fn("lo", 0.004), lanes=(2, 3), prio=1,
                       period_s=0.03, n_jobs=15))
    stats = ex.run(1.2)
    # the gang-isolation barrier drains other gangs' in-flight quanta before
    # a new gang's quantum starts, so no cross-gang overlap is observable
    assert len(overlap) == 0, overlap
    assert len(stats["response_times"]["hi"]) >= 10
    assert ex.sched.check_invariant()


def test_executor_throttles_best_effort():
    """BE quanta admitted only within the running gang's byte budget."""
    def busy(lane, idx):
        time.sleep(0.004)

    def be_quantum(lane):
        time.sleep(0.0005)

    results = {}
    for budget in (0.0, 1e9):
        ex = GangExecutor(n_lanes=2, regulation_interval_s=0.01)
        ex.submit_rt(RTJob("rt", busy, lanes=(0,), prio=5, period_s=0.005,
                           budget_bytes=budget, n_jobs=100))
        ex.submit_be(BEJob("be", be_quantum, lanes=(1,),
                           bytes_per_quantum=1000.0))
        stats = ex.run(0.8)
        results[budget] = stats["be_quanta"]["be"]
    assert results[0.0] < results[1e9] * 0.2, results


@pytest.mark.parametrize("failing", ["rt", "be"])
def test_executor_run_reraises_quantum_failure(failing):
    """A quantum that raises ends the run early, and run() re-raises it."""
    class Boom(RuntimeError):
        pass

    def rt_fn(lane, idx):
        if failing == "rt" and idx == 3:
            raise Boom("rt")
        time.sleep(0.001)

    def be_fn(lane):
        if failing == "be":
            raise Boom("be")
        time.sleep(0.001)

    ex = GangExecutor(n_lanes=2, regulation_interval_s=0.01)
    ex.submit_rt(RTJob("rt", rt_fn, lanes=(0,), prio=5, period_s=0.005,
                       budget_bytes=1e9, n_jobs=100))
    ex.submit_be(BEJob("be", be_fn, lanes=(1,), bytes_per_quantum=1.0))
    t0 = time.monotonic()
    with pytest.raises(Boom, match=failing):
        ex.run(5.0)
    assert time.monotonic() - t0 < 4.0


def test_executor_records_stragglers():
    """A straggling quantum stands out in the run's flight record as
    its release's ``rt.run`` phase."""
    slow = {"n": 0}

    def fn(lane, idx):
        slow["n"] += 1
        time.sleep(0.05 if slow["n"] == 10 else 0.001)

    ex = GangExecutor(n_lanes=1)
    ex.submit_rt(RTJob("j", fn, lanes=(0,), prio=5, period_s=0.005,
                       n_jobs=20))
    ex.run(0.6)
    runs = {p.k: p.run for p in flight.last_run().releases if p.job == "j"}
    assert sorted(runs) == list(range(20))
    assert max(runs, key=runs.get) == 9 and runs[9] >= 0.05
    assert float(np.median(list(runs.values()))) < 0.025
