"""The step-telemetry ring's compile hook
(``steptrace.install_compile_listener``): every compilation jax makes in
the process lands as one record for each part jax times (``trace``,
``lower``, ``backend``), under the function's name, with jax's own start
and end and, on ``backend``, the persistent cache's verdict.

On the CPU, with the persistent cache in a ``tmp_path`` and its thresholds
lowered so that a toy function is kept. No assertion on a duration beyond
its sign.
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring
from jax.experimental.compilation_cache import compilation_cache as cc

from ray_tpu._private import steptrace
from ray_tpu.parallel import train_step

pytestmark = pytest.mark.steptrace

PARTS = ["trace", "lower", "backend"]
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_OPTIONS = {"jax_compilation_cache_dir": None,
                  "jax_enable_compilation_cache": True,
                  "jax_persistent_cache_min_compile_time_secs": 0,
                  "jax_persistent_cache_min_entry_size_bytes": -1}


@pytest.fixture(autouse=True)
def _ring_and_cache(tmp_path):
    """A fresh ring with the hook installed, and a persistent cache of this
    test's own that keeps every entry."""
    old = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    for name, value in _CACHE_OPTIONS.items():
        jax.config.update(name, str(tmp_path) if value is None else value)
    cc.reset_cache()
    jax.clear_caches()
    steptrace.set_enabled(True)
    steptrace.reset()
    steptrace.install_compile_listener()
    yield
    for name, value in old.items():
        jax.config.update(name, value)
    cc.reset_cache()
    jax.clear_caches()
    steptrace.set_enabled(True)
    steptrace.reset()
    steptrace.clear_train_context()


def _compiles(name=None):
    return [r for r in steptrace.snapshot() if r["kind"] == "compile"
            and (name is None or r["name"] == name)]


def _probe(scale):
    """A jitted function no other test has compiled (its own name)."""
    def fn(x):
        return jnp.tanh(x) * scale
    fn.__name__ = f"probe_{scale}"
    return jax.jit(fn), fn.__name__


def test_parts_carry_the_function_and_miss_then_hit():
    steptrace.set_train_context(rank=2, world=4)
    steptrace.step_mark()  # the compilations below fall into step 1
    fn, name = _probe(3)
    x = jnp.ones((8, 8))
    fn(x).block_until_ready()
    first = _compiles(name)
    assert [r["part"] for r in first] == PARTS  # "fn" and "jit(fn)": one form
    assert [r["cache"] for r in first] == [None, None, "miss"]
    assert first[2]["retrieval_s"] is None
    assert all((r["rank"], r["step"], r["first"]) == (2, 1, False)
               for r in first)
    jax.clear_caches()  # the in-memory executables go, the files stay
    fn(x).block_until_ready()
    again = _compiles(name)[3:]
    assert [r["part"] for r in again] == PARTS
    assert again[2]["cache"] == "hit"
    assert again[2]["retrieval_s"] >= 0.0


def test_a_cached_compile_leaves_no_saving_and_no_backward_interval():
    fn, name = _probe(5)
    x = jnp.ones((8, 8))
    fn(x).block_until_ready()
    jax.clear_caches()
    fn(x).block_until_ready()
    records = _compiles()
    assert {r["part"] for r in _compiles(name)} == set(PARTS)
    assert [r["cache"] for r in _compiles(name)
            if r["part"] == "backend"] == ["miss", "hit"]
    for r in records:
        assert r["end"] >= r["start"], r
        assert "compile_time_saved" not in r["name"], r
        assert r["name"] and r["part"] in PARTS, r
        assert (r["cache"] in ("hit", "miss", "uncached")) == (
            r["part"] == "backend"), r
    # jax's interval, on the ring's clock: inside this test's lifetime
    now = time.time()
    assert all(now - 300 < r["start"] <= now for r in records)


def test_a_cache_that_is_not_asked_reads_uncached():
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    fn, name = _probe(7)
    fn(jnp.ones((8, 8))).block_until_ready()
    assert [(r["part"], r["cache"]) for r in _compiles(name)] == [
        ("trace", None), ("lower", None), ("backend", "uncached")]


def test_a_verdict_stays_in_the_thread_that_compiles():
    """The cache raises its verdict inside the backend part, before that
    part's own event. A function whose backend part ends in another thread
    meanwhile must not take it."""
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)

    def elsewhere():
        monitoring.record_event_time_span(BACKEND_EVENT, 10.0, 11.0,
                                          fun_name="jit(other)")

    thread = threading.Thread(target=elsewhere)
    thread.start()
    thread.join(timeout=30)
    assert not thread.is_alive()
    monitoring.record_event_time_span(BACKEND_EVENT, 10.0, 12.0,
                                      fun_name="jit(mine)")
    # and the verdict is spent: the next part in this thread starts clean
    monitoring.record_event_time_span(BACKEND_EVENT, 12.0, 13.0,
                                      fun_name="jit(next)")
    assert [(r["name"], r["cache"], r["retrieval_s"]) for r in _compiles()] == [
        ("other", "uncached", None), ("mine", "hit", 0.25),
        ("next", "uncached", None)]
    # a real compilation in a second thread gets its own verdict
    fn, name = _probe(11)
    thread = threading.Thread(
        target=lambda: fn(jnp.ones((8, 8))).block_until_ready())
    thread.start()
    thread.join(timeout=120)
    assert not thread.is_alive()
    assert [r["cache"] for r in _compiles(name)] == [None, None, "miss"]


def test_switched_off_nothing_is_recorded_and_no_verdict_is_left_over():
    fn, name = _probe(13)
    before = steptrace.record_calls()
    steptrace.set_enabled(False)
    fn(jnp.ones((8, 8))).block_until_ready()  # a miss, while off
    assert steptrace.record_calls() == before
    assert _compiles() == []
    steptrace.set_enabled(True)
    monitoring.record_event_time_span(BACKEND_EVENT, 1.0, 2.0,
                                      fun_name="jit(later)")
    assert [(r["name"], r["cache"]) for r in _compiles()] == [
        ("later", "uncached")]


def test_only_the_parts_jax_times_are_kept():
    """A saving is no interval (``compile_time_saved_sec`` may be negative)
    and other events of the compile path are not parts."""
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", -0.002)
    monitoring.record_event_duration_secs(BACKEND_EVENT, 1.0,
                                          fun_name="jit(f)")
    monitoring.record_event_time_span(
        "/jax/core/compile/some_later_part_duration", 1.0, 2.0,
        fun_name="jit(f)")
    assert _compiles() == []


def test_chrome_trace_names_a_slice_by_function_and_part():
    fn, name = _probe(17)
    fn(jnp.ones((8, 8))).block_until_ready()
    steptrace.record_compile("collective.allreduce", 5.0, 6.0, first=True)
    merged = steptrace.merge_records(steptrace.snapshot())
    assert [r["part"] for r in merged["compiles"]
            if r["name"] == name] == PARTS
    slices = {e["name"]: e for e in steptrace.chrome_trace(merged)
              if e.get("cat") == "compile"}
    assert {f"{name} [{part}]" for part in PARTS} <= set(slices)
    backend = slices[f"{name} [backend]"]
    assert backend["tid"] == "compile"
    assert backend["args"] == {"first_call": False, "step": 0,
                               "part": "backend", "cache": "miss"}
    # the collective backend's own record keeps its shape and its name
    assert slices["collective.allreduce"]["args"] == {"first_call": True,
                                                      "step": 0}


def test_the_train_step_compiles_under_its_exported_name():
    """``parallel.build_train_step`` jits its step under
    ``train_step.STEP_NAME``: the name a reader of the ring looks for."""
    import optax

    step = train_step.build_train_step(
        lambda params, batch: jnp.mean((batch @ params["w"]) ** 2),
        optax.sgd(0.1), donate=False)
    params = {"w": jnp.ones((4, 2))}
    opt_state = optax.sgd(0.1).init(params)
    step.lower(params, opt_state, jnp.ones((3, 4))).compile()
    assert [r["part"] for r in _compiles(train_step.STEP_NAME)] == PARTS


def test_of_nested_traces_only_the_outermost_is_kept():
    """A function's trace holds the trace of every jitted function it
    calls, ``jnp``'s own among them: a 48-layer step's 12,000 would push
    everything else out of the ring."""
    inner, inner_name = _probe(19)

    def outer(x):
        return inner(x) + jnp.cos(x)

    outer.__name__ = "probe_outer"
    x, y = jnp.ones((8, 8)), jnp.ones((4, 4))
    steptrace.reset()  # what making the arguments compiled
    jax.jit(outer)(x).block_until_ready()
    assert [(r["name"], r["part"]) for r in _compiles()] == [
        ("probe_outer", part) for part in PARTS]
    # called on its own, the inner function is a compilation like any other
    inner(y).block_until_ready()
    assert [r["part"] for r in _compiles(inner_name)] == PARTS
