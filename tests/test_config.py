"""Flag table semantics (ray parity: RAY_CONFIG env/system_config layering,
src/ray/common/ray_config_def.h) and wiring into live components."""

import os
import subprocess
import sys

import pytest

from ray_tpu._private.config import GLOBAL_CONFIG


def test_defaults_and_update():
    assert GLOBAL_CONFIG.rpc_max_message_bytes == 1 << 31
    assert GLOBAL_CONFIG.tune_experiment_snapshot_period_s == 10.0
    GLOBAL_CONFIG.update({"rpc_auth_timeout_s": 3.5})
    try:
        assert GLOBAL_CONFIG.rpc_auth_timeout_s == 3.5
    finally:
        GLOBAL_CONFIG.reset()


def test_unknown_flag_rejected():
    import pytest

    with pytest.raises(ValueError, match="Unknown system config"):
        GLOBAL_CONFIG.update({"definitely_not_a_flag": 1})


def test_env_override_in_subprocess():
    """RAY_TPU_<NAME> env vars override defaults at process start."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu._private.config import GLOBAL_CONFIG;"
         "print(GLOBAL_CONFIG.serve_control_loop_period_s,"
         "      GLOBAL_CONFIG.gcs_store_fsync)"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ,
             "RAY_TPU_serve_control_loop_period_s": "0.75",
             "RAY_TPU_gcs_store_fsync": "true",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__)))},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["0.75", "True"]


def test_flag_wiring_serve_graceful_default():
    """Flags are read at use time, not frozen at import: changing the flag
    changes freshly built DeploymentConfigs."""
    from ray_tpu.serve._common import DeploymentConfig

    GLOBAL_CONFIG.update({"serve_default_graceful_shutdown_timeout_s": 2.0})
    try:
        assert DeploymentConfig(name="x").graceful_shutdown_timeout_s == 2.0
    finally:
        GLOBAL_CONFIG.reset()
    assert DeploymentConfig(name="x").graceful_shutdown_timeout_s == 5.0


def test_flag_wiring_rpc_message_cap():
    from ray_tpu._private import rpcio

    GLOBAL_CONFIG.update({"rpc_max_message_bytes": 123})
    try:
        assert rpcio._max_msg() == 123
    finally:
        GLOBAL_CONFIG.reset()


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sources(top: str, but: str = ""):
    for root, _, files in os.walk(top):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and path != but:
                with open(path, encoding="utf-8") as f:
                    yield f.read()


def test_every_declared_flag_is_read_somewhere():
    """A flag that no file under ``ray_tpu/`` reads does nothing when it is
    set: delete it, or wire it up. A read is an attribute of the table
    (``cfg.<name>``, ``GLOBAL_CONFIG.<name>``) or a ``getattr`` by the
    name; the bare word in a comment is none."""
    import re

    from ray_tpu._private import config

    read = re.compile(
        r"(?:\bcfg|GLOBAL_CONFIG)\.(\w+)|getattr\([^,()]+,\s*[\"'](\w+)[\"']")
    names = set()
    for text in _sources(os.path.join(_repo_root(), "ray_tpu"),
                         but=config.__file__):
        for attr, by_name in read.findall(text):
            names.add(attr or by_name)
    assert sorted(set(config._FLAG_DEFS) - names) == []


# Switches that no test turns to their other side, each with why it stays
# (ROADMAP.md Design 5 is this list in prose). A switch that is neither
# named under tests/ nor listed here has a side that nothing runs: delete
# that side and the switch (PR 47 did so for twelve).
_UNFLIPPED_SWITCHES = {
    "tpu_autodetect": "off, and turned on by nothing: removing it means "
                      "choosing a default behaviour (ROADMAP Reach A6)",
    "serve_llm_real_model": "off, and turned on by nothing: which engine "
                            "serves by default is ROADMAP Design 6's",
    "metrics_enabled": "the start value of metrics_core.set_enabled(), "
                       "which tests flip: one switch with Design 8's ring",
    "memview_enabled": "the start value of memview.set_enabled(), which "
                       "tests flip: Design 8",
    "reqtrace_enabled": "the start value of reqtrace.set_enabled(), which "
                        "tests flip: Design 8",
}


def test_every_switch_is_flipped_by_a_test():
    """Every ``bool`` flag, and every ``str`` flag whose default names a
    mode, is named by a file under ``tests/`` (something turns it to its
    other side) or stands in ``_UNFLIPPED_SWITCHES`` with its reason."""
    import re

    from ray_tpu._private import config

    # a mode name: a bare lower-case word (paths, URIs, coordinates and
    # the empty string are values, not modes)
    switches = {
        name for name, (typ, default) in config._FLAG_DEFS.items()
        if typ is bool or (typ is str and re.fullmatch(r"[a-z][a-z0-9_]*",
                                                       default))
    }
    words = set()
    for text in _sources(os.path.join(_repo_root(), "tests")):
        # this file's own list names the unflipped ones: leave it out
        text = re.sub(r"(?s)_UNFLIPPED_SWITCHES = \{.*?\n\}\n", "", text)
        words.update(w.removeprefix("RAY_TPU_")
                     for w in re.findall(r"\w+", text))
    unflipped = switches - words
    assert sorted(unflipped - set(_UNFLIPPED_SWITCHES)) == []
    # and the list holds nothing that is gone or that a test now names
    assert sorted(set(_UNFLIPPED_SWITCHES) - unflipped) == []


@pytest.mark.parametrize("fsync", [False, True])
def test_gcs_store_fsync_reaches_the_log_store(tmp_path, monkeypatch, fsync):
    """``make_store`` hands the flag to whichever log store it builds."""
    from ray_tpu._private import gcs_store

    monkeypatch.delenv("RAY_TPU_GCS_STORAGE", raising=False)
    monkeypatch.setitem(GLOBAL_CONFIG._values, "gcs_store_fsync", fsync)
    store = gcs_store.make_store(str(tmp_path / "gcs.log"))
    try:
        assert isinstance(store, (gcs_store.FileLogStore,
                                  gcs_store.NativeLogStore))
        assert store.fsync is fsync
    finally:
        store.close()
