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


def test_every_declared_flag_is_read_somewhere():
    """A flag that no file under ``ray_tpu/`` but the table names does
    nothing when it is set: delete it, or wire it up."""
    import re

    from ray_tpu._private import config

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(config.__file__)))
    words = set()
    for root, _, files in os.walk(pkg):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and path != config.__file__:
                with open(path, encoding="utf-8") as f:
                    words.update(re.findall(r"\w+", f.read()))
    assert sorted(set(config._FLAG_DEFS) - words) == []


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
