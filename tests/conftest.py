"""Test fixtures (analog of ray: python/ray/tests/conftest.py).

``ray_start_regular`` spins a real single-node cluster (GCS + raylet
subprocesses) per test module; ``ray_start_cluster`` provides the multi-node
Cluster fixture. JAX-using tests force an 8-device virtual CPU mesh so
multi-chip sharding is exercised without TPU hardware.
"""

import os

# Virtual 8-device CPU mesh for sharding tests. Both variables are read when
# jax is first imported, so they are set here, before any test module loads;
# cluster processes the fixtures spawn inherit them. Force-override: tests
# must never take the chip (chip_smoke.py and perfbench/run.py run outside
# pytest and do).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import time

import pytest


def pytest_configure(config):
    # registered here as well as pytest.ini so `pytest tests/test_x.py`
    # from any cwd stays warning-free
    config.addinivalue_line(
        "markers", "slow: heavy/long test, excluded from the tier-1 lane")
    config.addinivalue_line(
        "markers",
        "chaos: kill/partition/fault-injection chaos test "
        "(run the heavy ones via scripts/run_chaos.sh)")
    config.addinivalue_line(
        "markers",
        "metrics: metrics-plane test (metrics_core, scrape fan-out, "
        "overhead gate)")
    config.addinivalue_line(
        "markers",
        "logs: log-plane test (attribution spans, streaming dedup, "
        "tail/range surfaces)")
    config.addinivalue_line(
        "markers",
        "train_ft: elastic-training fault-tolerance test (watchdog, "
        "epoch-keyed re-formation, checkpointed recovery, drain)")


def wait_for_condition(condition, timeout: float = 30.0,
                       retry_interval_ms: float = 100.0, **kwargs):
    """Poll ``condition(**kwargs)`` until truthy (analog of ray:
    _private/test_utils.py wait_for_condition). Raises RuntimeError with
    the last exception on timeout. Use this instead of fixed sleeps:
    restarts are awaited, not guessed."""
    deadline = time.monotonic() + timeout
    last_exc = None
    while time.monotonic() < deadline:
        try:
            if condition(**kwargs):
                return
            last_exc = None
        except Exception as e:  # flaky probes retry until the deadline
            last_exc = e
        time.sleep(retry_interval_ms / 1000.0)
    suffix = f" (last exception: {last_exc!r})" if last_exc else ""
    raise RuntimeError(
        f"condition {getattr(condition, '__name__', condition)!r} not met "
        f"within {timeout}s{suffix}")


def kernel_calls(jaxpr):
    """How often each Pallas kernel stands in ``jaxpr`` (a ``make_jaxpr``
    result) and in what it calls, by the ``pallas_call``'s ``name``
    -> a ``collections.Counter``."""
    import collections

    import jax  # not at import time: this file sets jax's environment

    found = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] += 1
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jaxpr.jaxpr)
    return found


def kernel_whiles(jaxpr):
    """``while`` equations inside the Pallas kernels of ``jaxpr``: what a
    ``fori_loop`` with a traced bound is traced to (static bounds give a
    ``scan``, a Python loop nothing)."""
    import jax  # not at import time: this file sets jax's environment

    found = []

    def walk(jaxpr, in_kernel):
        for eqn in jaxpr.eqns:
            here = in_kernel or eqn.primitive.name == "pallas_call"
            if in_kernel and eqn.primitive.name == "while":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jaxpr.jaxpr, False)
    return found


# --- shared-cluster fast lane -------------------------------------------
# Booting GCS + raylet + workers costs ~10-13s; with ~40 modules that is
# minutes of pure boot. ray_start_regular therefore REUSES the previous
# module's live cluster when (a) the module doesn't opt out with
# `RAY_REUSE_CLUSTER = False` at module scope, and (b) the cluster passes
# a health probe (full CPU capacity free, API responsive) — a module that
# crashed mid-test and leaked actors recycles instead of poisoning its
# successors. Fixtures that need a pristine or multi-node cluster tear
# the shared one down first.
_shared_cluster = {"active": False}


def _teardown_shared():
    if _shared_cluster["active"]:
        import ray_tpu

        _shared_cluster["active"] = False
        ray_tpu.shutdown()


def _shared_cluster_healthy() -> bool:
    import ray_tpu

    try:
        avail = ray_tpu.available_resources()
        total = ray_tpu.cluster_resources()
        # all CPUs free again = the previous module cleaned up after itself
        return avail.get("CPU", 0) >= total.get("CPU", 0) - 0.01
    except Exception:
        return False


@pytest.fixture(scope="module")
def ray_start_regular(request):
    import ray_tpu

    reuse_ok = getattr(request.module, "RAY_REUSE_CLUSTER", True)
    if _shared_cluster["active"]:
        if reuse_ok and _shared_cluster_healthy():
            yield  # adopt the live cluster; leave it for the next module
            return
        _teardown_shared()
    ray_tpu.init(num_cpus=4, resources={"custom": 2.0})
    if reuse_ok:
        _shared_cluster["active"] = True
        yield  # stays alive for the next reuse-ok module
    else:
        yield
        ray_tpu.shutdown()


@pytest.fixture(scope="session", autouse=True)
def _shared_cluster_finalizer():
    yield
    _teardown_shared()


@pytest.fixture(scope="module", autouse=True)
def _isolate_self_managed_modules(request):
    """Modules that call ray_tpu.init()/Cluster() themselves (their own
    fixtures, custom env vars) must not inherit a live shared cluster —
    their init would collide with the existing driver connection."""
    import inspect
    import re

    try:
        src = inspect.getsource(request.module)
    except (OSError, TypeError):
        src = ""
    overrides_fixture = ("def ray_start_regular" in src
                         or "def ray_start_cluster" in src)
    # whole names: ray_start_regular_fn tears the shared cluster down
    # itself and says nothing about the module's other tests
    uses_conftest_fixture = (not overrides_fixture and re.search(
        r"\bray_start_(regular|cluster)\b", src) is not None)
    inits_itself = "ray_tpu.init(" in src or "Cluster(" in src
    if (overrides_fixture or inits_itself) and not uses_conftest_fixture:
        _teardown_shared()
    yield


@pytest.fixture
def ray_start_regular_fn():
    """Function-scoped variant for tests that mutate cluster state."""
    import ray_tpu

    _teardown_shared()
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    _teardown_shared()
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    import ray_tpu

    ray_tpu.shutdown()
    cluster.shutdown()
