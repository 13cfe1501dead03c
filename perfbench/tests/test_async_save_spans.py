"""A save that commits behind the steps (``save_pytree`` inside a train
session) and the span readers: the loop of perfbench/worker.py in small,
traced on the CPU with the runtime's real spans and a writer slowed so that
the write and the late hand-over of the checkpoint fall between the steps
of the traced window. ``progspans.align`` must still lay the ring on the
trace (``save/commit`` carries none of the prefixes it holds to a
``bench/*`` span, the hand-over records no ``train/report``), and every
reader of the checkpoint layer must find its span.
"""

import os
import time


from perfbench import progspans, worker, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
STEPS, PERIODS, TAIL = 8, 2, 8


def test_spans_of_a_save_behind_the_steps_align(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from orbax.checkpoint._src.checkpointers import async_checkpointer

    from ray_tpu import train
    from ray_tpu._private import steptrace
    from ray_tpu.air import checkpoint
    from ray_tpu.train import session

    real = async_checkpointer._background_wait_for_commit_futures
    monkeypatch.setattr(
        async_checkpointer, "_background_wait_for_commit_futures",
        lambda *a, **kw: time.sleep(0.08) or real(*a, **kw))
    steptrace.set_enabled(True)
    steptrace.reset()
    state = {"w": jnp.ones((256, 256), jnp.float32)}
    nbytes = 256 * 256 * 4

    def one_step(i):
        with jax.profiler.TraceAnnotation(worker.SPAN_STEP):
            time.sleep(0.02)
            train.report({"step": i})

    def save(k):
        with jax.profiler.TraceAnnotation(worker.SPAN_CKPT):
            target = str(tmp_path / f"save_{k}")
            checkpoint.save_pytree(state, target, name="state")
            train.report({"saved": k},
                         checkpoint=checkpoint.Checkpoint.from_directory(
                             target))

    s = session.init_session(session.TrainContext(0, 1), None)
    try:
        save(0)  # the warm-up's: orbax is imported, and its commit ends
        checkpoint.finish_commit()  # before the window opens
        while not s.queue.empty():
            s.queue.get()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=options)
        for period in range(PERIODS):
            for i in range(STEPS):
                one_step(i)
            save(1 + period)
        for i in range(TAIL):  # the last commit ends among these
            one_step(i)
        checkpoint.finish_commit()
        jax.profiler.stop_trace()
        snap = steptrace.process_snapshot()
        handed = []
        while not s.queue.empty():
            handed.append(s.queue.get())
    finally:
        session.shutdown_session()
        steptrace.reset()

    # the checkpoints came late, after reports of later steps, in order
    kinds = [(m["type"], m["metrics"].get("saved")) for m in handed]
    assert [k for k in kinds if k[0] == "checkpoint"] == [
        ("checkpoint", 1), ("checkpoint", 2)]
    assert kinds.index(("checkpoint", 1)) > kinds.index(("report", 1)) + 1

    trace = xplane.load(xplane.find_xplane(str(tmp_path / "trace")))
    spans = progspans.align(trace, snap["records"], snap["dropped"])
    assert spans is not None
    steps = xplane.spans_named(trace, worker.SPAN_STEP)
    saves = xplane.spans_named(trace, worker.SPAN_CKPT)
    assert (len(steps), len(saves)) == (STEPS * PERIODS + TAIL, PERIODS)
    assert len(spans["train/report"]) == len(steps) + len(saves)
    for name in ("ckpt/setup", "ckpt/commit", "ckpt/snapshot"):
        assert len(spans[name]) == PERIODS
        for start, end, _ in spans[name]:
            assert any(s0 - progspans.TOLERANCE_NS <= start
                       and end <= e0 + progspans.TOLERANCE_NS
                       for s0, e0 in saves), name
    # what the loop waited for: nothing at the first save of the window,
    # the first save's bytes at the second
    assert [n for _, _, n in spans["ckpt/commit"]] == [None, nbytes]
    # the writes lie behind the steps: each starts where its save's
    # snapshot ended and ends outside every bench/ckpt span
    written = spans["save/commit"]
    assert [n for _, _, n in written] == [nbytes, nbytes]
    for (start, end, _), (_, snapshot_end, _) in zip(
            written, spans["ckpt/snapshot"]):
        assert start >= snapshot_end
        assert not any(s0 <= end <= e0 for s0, e0 in saves)
        assert xplane.overlap([(start, end)], steps) > 0

    reading = worker._Reading(trace=trace, _program_spans=spans)
    values = {name: worker._load_reader(ROOT, "perfbench/metrics", name)
              .read(reading)
              for name in ("ckpt_setup_ms", "ckpt_snapshot_ms",
                           "ckpt_commit_ms", "ckpt_stall_ms",
                           "report_ms.job")}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the parts lie inside what times them from outside
    assert (values["ckpt_setup_ms"] + values["ckpt_snapshot_ms"]
            + values["ckpt_commit_ms"] <= values["ckpt_stall_ms"])
    # and the write itself, 80 ms and more a save, is in none of them
    assert progspans.total_ms_per(reading, "save/commit",
                                  worker.SPAN_CKPT) >= 80.0
