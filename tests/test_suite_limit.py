"""tests/conftest.py's limit on one test's seconds, with a tiny limit patched
in: in a run of its own (`pytester` is not loaded by the tier-1 command)."""

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

CONFTEST = """
import importlib.util
spec = importlib.util.spec_from_file_location("suite_conftest", {path!r})
suite = importlib.util.module_from_spec(spec)
spec.loader.exec_module(suite)
suite.TEST_LIMIT_S = 0.5
pytest_runtest_setup = suite.pytest_runtest_setup
pytest_runtest_teardown = suite.pytest_runtest_teardown
"""

TESTS = """
import time
import pytest

@pytest.fixture
def slow_to_set_up():
    time.sleep(30)

def test_quick():
    pass

def test_sleeps():
    time.sleep(30)

def test_setup_sleeps(slow_to_set_up):
    pass

def test_quick_after_them():
    pass
"""


def test_a_test_over_the_limit_fails_by_name_and_the_rest_run(tmp_path):
    (tmp_path / "conftest.py").write_text(CONFTEST.format(
        path=os.path.join(HERE, "conftest.py")))
    (tmp_path / "test_limit.py").write_text(TESTS)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "test_limit.py", "-q", "-rf",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    text = out.stdout + out.stderr
    assert out.returncode == 1, text
    assert "1 failed, 2 passed, 1 error" in text, text  # a setup errors
    for name in ("test_sleeps", "test_setup_sleeps"):
        assert (f"test_limit.py::{name} took more than 0.5 s (TEST_LIMIT_S"
                in text), text
