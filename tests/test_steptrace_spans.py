"""The runtime's own spans (``steptrace.span``): one ring record each,
and in a process that holds jax one ``ray_tpu/<name>`` event in whatever
profiler trace is being taken, on that trace's clock. Where each span is
placed: ``train/report`` (session.report), ``data/next`` around
``data/fetch`` (the batch iterator), ``ckpt/setup|snapshot|commit``
(save_pytree), ``train/shard_state`` (gpt2.shard_train_state).
``ckpt/persist`` (the driver) is covered by the e2e test in
test_steptrace.py.

No assertion on a duration: only on what is recorded, in which order,
with which count, and that the two clocks differ by one constant.
"""

import statistics
import subprocess
import sys

import numpy as np
import pytest

from ray_tpu._private import steptrace

pytestmark = pytest.mark.steptrace


@pytest.fixture(autouse=True)
def _fresh_ring():
    steptrace.set_enabled(True)
    steptrace.reset()
    steptrace.clear_train_context()
    yield
    steptrace.set_enabled(True)
    steptrace.reset()
    steptrace.clear_train_context()


def _spans(prefix=""):
    return [r for r in steptrace.snapshot()
            if r["kind"] == "phase" and r["phase"].startswith(prefix)]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_records_count_and_the_step_it_was_entered_in():
    steptrace.set_train_context(rank=2, world=4)
    with steptrace.span("data/fetch", 7):
        pass
    with steptrace.span("data/next") as sp:
        steptrace.step_mark()  # a report inside a span closes step 0
        sp.n = 16
    with steptrace.span("compute"):
        pass
    assert [(r["phase"], r["step"], r["rank"], r["n"]) for r in _spans()] == [
        ("data/fetch", 0, 2, 7), ("data/next", 0, 2, 16),
        ("compute", 1, 2, None)]
    assert all(r["end"] >= r["start"] for r in _spans())
    # the count reaches the operator's timeline
    trace = steptrace.chrome_trace(steptrace.merge_records(
        steptrace.snapshot()))
    args = {e["name"]: e["args"] for e in trace if e.get("cat") == "phase"}
    assert args["data/next"] == {"step": 0, "n": 16}
    assert args["compute"] == {"step": 1}


def test_spans_are_host_events_of_a_profiler_trace_on_its_clock(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    jax.numpy.zeros(1).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(12):
            with steptrace.span("unit/outer", i):
                with steptrace.span("unit/inner"):
                    np.ones(20_000).sum()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("ray_tpu/unit/"))
    records = sorted(_spans("unit/"), key=lambda r: r["start"])
    assert len(events) == len(records) == 24
    assert [name for _, _, name in events] == [
        "ray_tpu/" + r["phase"] for r in records]
    # one clock by construction: a single offset lays every record on its
    # event (1 ms is the tolerance ISSUE 24 sets; the edges are stamped
    # back to back)
    offset = statistics.median(r["start"] - s / 1e9
                               for r, (s, _, _) in zip(records, events))
    for r, (s, e, _) in zip(records, events):
        assert abs(r["start"] - offset - s / 1e9) < 1e-3
        assert abs(r["end"] - offset - e / 1e9) < 1e-3


def test_a_span_never_imports_jax():
    code = (
        "import sys\n"
        "import ray_tpu\n"
        "from ray_tpu._private import steptrace\n"
        "from ray_tpu.train import backend_executor, session\n"
        "with steptrace.span('ckpt/persist', 3):\n"
        "    pass\n"
        "recs = steptrace.snapshot()\n"
        "assert [(r['phase'], r['n']) for r in recs] == "
        "[('ckpt/persist', 3)], recs\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


class _CountingAnnotation:
    """Stands where jax.profiler.TraceAnnotation would."""

    made = []

    def __init__(self, name):
        self.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_disabled_no_record_and_no_annotation(tmp_path, monkeypatch):
    from ray_tpu.air.checkpoint import save_pytree
    from ray_tpu.train import session

    monkeypatch.setattr(steptrace, "_annotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "made", [])
    with steptrace.span("on"):
        pass
    assert _CountingAnnotation.made == ["ray_tpu/on"]
    before, calls = steptrace.snapshot(), steptrace.record_calls()

    steptrace.set_enabled(False)
    session.init_session(session.TrainContext(0, 1), None)
    try:
        with steptrace.span("off", 1):
            pass
        session.report({"loss": 1.0})
        save_pytree({"w": np.ones(4, np.float32)}, str(tmp_path))
    finally:
        session.shutdown_session()
    assert _CountingAnnotation.made == ["ray_tpu/on"]
    assert steptrace.record_calls() == calls
    assert steptrace.snapshot() == before


# ---------------------------------------------------------------------------
# where the runtime records them
# ---------------------------------------------------------------------------

def test_report_records_train_report_with_the_step_it_closed():
    from ray_tpu.train import session

    s = session.init_session(session.TrainContext(1, 2), None)
    try:
        session.report({"loss": 1.0})
        with steptrace.span("data/next"):
            pass
        session.report({"loss": 0.5})
    finally:
        session.shutdown_session()
    assert s.queue.qsize() == 2
    assert [(r["phase"], r["step"], r["rank"]) for r in _spans()] == [
        ("train/report", 0, 1), ("data/next", 1, 1), ("train/report", 1, 1)]
    steps = [r for r in steptrace.snapshot() if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [0, 1]
    # the step mark lies inside the span that reports it
    for mark, rep in zip(steps, _spans("train/report")):
        assert rep["start"] <= mark["end"] <= rep["end"]
    # outside a session nothing is reported and nothing recorded
    steptrace.reset()
    assert session.report({"loss": 0.0}) == {"loss": 0.0}
    assert steptrace.snapshot() == []


@pytest.mark.parametrize("source", ["dataset", "streaming_split"])
def test_iter_batches_records_next_per_batch_and_fetch_per_block(
        ray_start_regular, source):
    from ray_tpu import data

    # 3 blocks of 5 rows, batches of 4: 3 whole batches and one of 3
    ds = data.from_numpy([np.arange(5 * i, 5 * i + 5) for i in range(3)],
                         column="x")
    it = ds.iterator() if source == "dataset" else ds.streaming_split(1)[0]
    steptrace.reset()
    batches = list(it.iter_batches(batch_size=4))
    assert [len(b["x"]) for b in batches] == [4, 4, 4, 3]
    assert sorted(np.concatenate([b["x"] for b in batches])) == list(range(15))

    nexts, fetches = _spans("data/next"), _spans("data/fetch")
    # one per batch handed out, rows as the count, and the call that found
    # the stream drained
    assert [r["n"] for r in nexts] == [4, 4, 4, 3, 0]
    if source == "dataset":
        assert [r["n"] for r in fetches] == [5, 5, 5]
    else:
        # per block one wait on the coordinator and one on the object
        # plane, then the coordinator's "epoch over"
        assert [r["n"] for r in fetches] == [5, 5, 5, 5, 5, 5, 0]
    # nesting is by name and by time: every fetch lies inside a next
    for f in fetches:
        assert any(n["start"] <= f["start"] and f["end"] <= n["end"]
                   for n in nexts), (f, nexts)


@pytest.mark.parametrize("path", ["orbax", "msgpack"])
def test_save_pytree_records_setup_snapshot_commit(tmp_path, monkeypatch,
                                                   path):
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from ray_tpu.air.checkpoint import load_pytree, save_pytree

    if path == "orbax":
        pytest.importorskip("orbax.checkpoint")
    else:
        monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)
    tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones(5, jnp.bfloat16)}
    save_pytree(tree, str(tmp_path), name="state")
    assert (tmp_path / ("state_orbax" if path == "orbax"
                        else "state.msgpack")).exists()
    spans = _spans("ckpt/")
    assert [r["phase"] for r in spans] == [
        "ckpt/setup", "ckpt/snapshot", "ckpt/commit"]
    nbytes = 12 * 4 + 5 * 2
    assert spans[0]["n"] is None and spans[1]["n"] == nbytes
    # commit counts what was written: the tree, or its msgpack encoding
    assert spans[2]["n"] == nbytes if path == "orbax" \
        else spans[2]["n"] >= nbytes
    for a, b in zip(spans, spans[1:]):
        assert a["end"] <= b["start"]
    monkeypatch.undo()
    back = load_pytree(str(tmp_path), tree, name="state")
    assert np.array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# train/shard_state (gpt2.shard_train_state)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fsdp", [False, True], ids=["replicated", "fsdp"])
def test_shard_train_state_records_one_span_with_the_bytes_placed(fsdp):
    import jax

    from ray_tpu import parallel
    from ray_tpu.models import gpt2

    _, params, _, opt_state = gpt2.make_train_state(
        gpt2.GPT2Config.small_test(), jax.random.PRNGKey(0))
    mesh = parallel.create_mesh({"fsdp": 4}, devices=jax.devices()[:4])
    placed = gpt2.shard_train_state(params, opt_state, mesh, fsdp=fsdp)
    span, = _spans("train/")
    assert span["phase"] == "train/shard_state"
    # float32 weights and both Adam moments, and optax's int32 step count
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert span["n"] == 3 * 4 * n_params + 4
    assert span["n"] == sum(x.nbytes for x in jax.tree.leaves(placed))
