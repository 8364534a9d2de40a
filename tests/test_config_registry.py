"""Config-flag registry (reference: RAY_CONFIG x-macro table,
src/ray/common/ray_config_def.h:17-22 — typed defaults, RAY_<name> env
overrides, _system_config overrides)."""
import pathlib
import re

import pytest

from ray_tpu._private.config import CONFIG


@pytest.fixture(autouse=True)
def fresh():
    CONFIG.reset()
    yield
    CONFIG.reset()


def test_defaults_and_attr_access():
    assert CONFIG.tracing_enabled is False
    assert CONFIG.serve_max_slots == 8
    assert CONFIG.get("transfer_chunk_bytes") == 4 * 1024 * 1024


def test_env_override(monkeypatch):
    monkeypatch.setenv("RAY_TPU_SERVE_MAX_SLOTS", "7")
    monkeypatch.setenv("RAY_TPU_DIRECT_TRANSPORT", "false")
    CONFIG.reset()
    assert CONFIG.serve_max_slots == 7
    assert CONFIG.direct_transport is False


def test_system_config_override_beats_env(monkeypatch):
    monkeypatch.setenv("RAY_TPU_LEASE_IDLE_S", "11")
    CONFIG.reset()
    CONFIG.apply_system_config({"lease_idle_s": 42.0})
    assert CONFIG.lease_idle_s == 42.0


def test_undeclared_flag_rejected():
    with pytest.raises(KeyError):
        CONFIG.get("no_such_flag")
    with pytest.raises(KeyError):
        CONFIG.apply_system_config({"no_such_flag": 1})


def test_dump_lists_every_flag():
    d = CONFIG.dump()
    assert "tracing_enabled" in d and "gcs_snapshot_period_s" in d
    assert len(d) == 45


def test_every_declared_flag_is_read():
    """A flag that is parsed and then read by nothing accepts a setting and
    ignores it.  A reader is ``CONFIG.<name>``, ``CONFIG.get("<name>")`` or
    the serve engine's ``_cfg("<name>", ...)`` in a file under ``ray_tpu/``
    other than the registry itself."""
    root = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu"
    registry = root / "_private" / "config.py"
    source = "\n".join(p.read_text() for p in sorted(root.rglob("*.py"))
                       if p != registry)
    reader = r"""CONFIG\.%s\b|(?:CONFIG\.get|_cfg)\(\s*["']%s["']"""
    unread = [name for name in CONFIG.dump()
              if not re.search(reader % (name, name), source)]
    assert not unread, f"declared in config.py and read nowhere: {unread}"


def test_system_config_string_bool_goes_through_parser():
    """'0'/'false' strings must disable a bool flag — bool('0') is True,
    which would silently invert the user's intent."""
    CONFIG.reset()
    CONFIG.apply_system_config({"direct_transport": "0"})
    assert CONFIG.direct_transport is False
    CONFIG.reset()
    CONFIG.apply_system_config({"tracing_enabled": "true"})
    assert CONFIG.tracing_enabled is True
    CONFIG.reset()
