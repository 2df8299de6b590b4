import threading
import time

import pytest

from repro.core import couler
from repro.core.caching import CacheStore, CoulerPolicy
from repro.core.engines.airflow import to_airflow_dag
from repro.core.engines.argo import ArgoSubmitter, to_argo_yaml
from repro.core.engines.base import StepStatus, TransientError
from repro.core.engines.cluster import Cluster, MultiClusterEngine
from repro.core.engines.local import LocalEngine
from repro.core.ir import Job, Resources, WorkflowIR


def test_retry_on_transient_error():
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise TransientError("TooManyRequestsErr: api-server busy")
        return "ok"

    with couler.workflow("flaky") as ir:
        couler.run_step(flaky, step_name="s", retry_limit=5)
    run = LocalEngine(retry_backoff_s=0.001).submit(ir)
    assert run.succeeded()
    assert run.steps["s"].attempts == 3


def test_permanent_error_fails_workflow():
    def boom():
        raise ValueError("not transient")

    with couler.workflow("boom") as ir:
        couler.run_step(boom, step_name="s", retry_limit=5)
    run = LocalEngine().submit(ir)
    assert not run.succeeded()
    assert run.steps["s"].attempts == 1         # no retry on permanent


def test_resume_from_failure_skips_done_steps():
    """App B.B: restart skips Succeeded/Cached; reruns the failed step."""
    state = {"fail": True, "a_runs": 0}

    def a():
        state["a_runs"] += 1
        return "A"

    def b(x):
        if state["fail"]:
            raise ValueError("crash")
        return x + "B"

    with couler.workflow("resume") as ir:
        oa = couler.run_step(a, step_name="a", cacheable=False)
        couler.run_step(b, oa, step_name="b", cacheable=False)
    eng = LocalEngine()
    run = eng.submit(ir)
    assert not run.succeeded()
    assert run.steps["a"].status == StepStatus.SUCCEEDED
    state["fail"] = False
    run2 = eng.resume(run)
    assert run2.succeeded()
    assert state["a_runs"] == 1                  # a NOT re-executed
    assert run2.artifacts["b:out"] == "AB"


def test_cache_skips_recompute_across_runs():
    calls = {"n": 0}

    def expensive():
        calls["n"] += 1
        return 42

    cache = CacheStore(capacity_bytes=1 << 20, policy=CoulerPolicy())
    eng = LocalEngine(cache=cache)

    def build():
        with couler.workflow("cached") as ir:
            couler.run_step(expensive, step_name="big")
        return ir

    r1 = eng.submit(build())
    r2 = eng.submit(build())
    assert calls["n"] == 1
    assert r2.steps["big"].status == StepStatus.CACHED
    assert r2.artifacts["big:out"] == 42


def test_straggler_speculation():
    slow_once = {"first": True}

    def maybe_slow():
        if slow_once["first"]:
            slow_once["first"] = False
            time.sleep(1.0)                     # straggler
            return "slow"
        return "fast"

    with couler.workflow("strag") as ir:
        couler.run_step(maybe_slow, step_name="s", est_time_s=0.02,
                        cacheable=False)
    eng = LocalEngine(straggler_factor=2.0)
    t0 = time.time()
    run = eng.submit(ir)
    assert run.succeeded()
    assert run.artifacts["s:out"] == "fast"     # speculative copy won
    assert run.steps["s"].speculative
    assert time.time() - t0 < 1.0


@pytest.mark.parametrize("device", ["declared", "array_input"])
def test_device_step_never_speculated(device):
    """A step on the device runs once even when it straggles: a second copy
    would be a second program on the same chip."""
    import jax.numpy as jnp
    calls = {"n": 0}

    def slow_device_step(x=None):
        calls["n"] += 1
        time.sleep(0.3)
        return calls["n"]

    with couler.workflow("device-strag") as ir:
        if device == "declared":
            couler.run_step(slow_device_step, step_name="s", est_time_s=0.01,
                            cacheable=False, resources=Resources(gpu=1))
        else:
            couler.run_step(slow_device_step, jnp.ones(4), step_name="s",
                            est_time_s=0.01, cacheable=False)
    eng = LocalEngine(straggler_factor=2.0)
    try:
        run = eng.submit(ir)
    finally:
        eng.close()
    assert run.succeeded()
    assert calls["n"] == 1
    assert run.steps["s"].attempts == 1
    assert not run.steps["s"].speculative


def test_parallelism_actually_parallel():
    barrier = threading.Barrier(4, timeout=5)

    def wait():
        barrier.wait()
        return 1

    with couler.workflow("par") as ir:
        couler.concurrent([
            lambda i=i: couler.run_step(wait, step_name=f"p{i}",
                                        cacheable=False)
            for i in range(4)])
    run = LocalEngine(max_workers=4, enable_speculation=False).submit(ir)
    assert run.succeeded()


def test_argo_yaml_generation_and_budget():
    with couler.workflow("y") as ir:
        a = couler.run_container(image="img:1", command=["run"], step_name="a")
        couler.run_container(image="img:2", command=["run"], step_name="b",
                             fn=None)
        couler.when(couler.equal(a, "x"),
                    lambda: couler.run_container(image="img:3", step_name="c"))
    y = to_argo_yaml(ir)
    assert "apiVersion: argoproj.io/v1alpha1" in y
    assert "dependencies: [a]" in y
    assert "when:" in y
    run = ArgoSubmitter().submit(ir)
    assert run.status == "Generated"
    assert len(run.artifacts["argo:manifests"]) == 1


def test_airflow_generation():
    with couler.workflow("af") as ir:
        a = couler.run_step(lambda: 1, step_name="a")
        couler.run_step(lambda x: x, a, step_name="b")
    src = to_airflow_dag(ir)
    assert "PythonOperator" in src and "t_a >> t_b" in src
    compile(src, "<dag>", "exec")               # syntactically valid python


def test_multicluster_scheduling_and_quota():
    wf = WorkflowIR("mc")
    for i in range(8):
        wf.add_job(Job(name=f"j{i}", est_time_s=1.0,
                       resources=Resources(cpu=4)))
    eng = MultiClusterEngine(clusters=[
        Cluster("a", cpu=8, mem_bytes=1 << 40),
        Cluster("b", cpu=8, mem_bytes=1 << 40),
    ])
    run = eng.submit(wf)
    assert run.succeeded()
    # 8 jobs x 4 cpu on 16 cpus -> 2 waves of 4 -> makespan 2s
    assert eng.metrics["makespan_s"] == pytest.approx(2.0)
    busy = eng.metrics["cluster_busy_s"]
    assert busy["a"] > 0 and busy["b"] > 0      # load balanced


def test_gpu_jobs_require_gpu_cluster():
    wf = WorkflowIR("gpu")
    wf.add_job(Job(name="g", est_time_s=1.0,
                   resources=Resources(cpu=1, gpu=1)))
    eng = MultiClusterEngine(clusters=[
        Cluster("cpu-only", cpu=64, mem_bytes=1 << 40, gpu=0),
        Cluster("gpu", cpu=64, mem_bytes=1 << 40, gpu=8),
    ])
    run = eng.submit(wf)
    assert run.succeeded()
    assert eng.metrics["cluster_busy_s"]["gpu"] > 0
    assert eng.metrics["cluster_busy_s"]["cpu-only"] == 0
