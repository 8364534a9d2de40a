"""Every tier-1 test has a limit of its own (tests/conftest.py): a hang in a
test's body, in a fixture's set-up or in a fixture's tear-down costs that one
test, and the run goes on.  Checked on a pytest of its own, which loads this
directory's conftest.py as a plugin over three tests that sleep far past a
one-second limit and one that does not."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

HANGING_TESTS = '''
import time

import pytest


@pytest.fixture
def slow_setup():
    time.sleep(60)
    yield


@pytest.fixture
def slow_teardown():
    yield
    time.sleep(60)


@pytest.mark.timeout(1)
def test_hangs_in_setup(slow_setup):
    pass


@pytest.mark.timeout(1)
def test_hangs_in_call():
    time.sleep(60)


@pytest.mark.timeout(1)
def test_hangs_in_teardown(slow_teardown):
    pass


def test_after_them():
    import conftest

    assert conftest.DEFAULT_TIMEOUT_S > 1
'''


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    where = tmp_path_factory.mktemp("limits")
    (where / "test_hanging.py").write_text(HANGING_TESTS)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [HERE, os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-q", "-rfE", "-W",
         "ignore::pytest.PytestUnknownMarkWarning", "--rootdir",
         str(where), "-c", os.devnull, str(where)],
        env=env, capture_output=True, text=True, timeout=120)
    return done.stdout + done.stderr


@pytest.mark.timeout(150)
@pytest.mark.parametrize("name, phase, outcome", [
    ("test_hangs_in_setup", "set-up", "ERROR"),
    ("test_hangs_in_call", "call", "FAILED"),
    ("test_hangs_in_teardown", "tear-down", "ERROR"),
])
def test_a_hang_costs_one_test(report, name, phase, outcome):
    assert f"::{name} ({phase}) passed its limit of 1.0 s" in report, report
    assert any(ln.startswith(outcome) and ln.endswith(name)
               for ln in report.splitlines()), report
    # ... and the run went on: the test after them ran, and passed (as did
    # the body of the one whose tear-down hangs).
    assert "1 failed, 2 passed" in report.splitlines()[-1], report
