"""The limit of `conftest.py` on one case, seen from outside: a child
`pytest` runs three cases under the same hooks with the limit at a fraction
of a second, so that the failure is the inner run's and not this one's."""

import os
import subprocess
import sys
import textwrap

import pytest

CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")

INNER_CONFTEST = f"""
    import importlib.util

    spec = importlib.util.spec_from_file_location("suite_conftest", {CONFTEST!r})
    suite = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(suite)
    suite.LIMIT = 0.25
    pytest_runtest_setup = suite.pytest_runtest_setup
    pytest_runtest_call = suite.pytest_runtest_call
    """

INNER_CASES = """
    import signal
    import time

    import pytest


    def test_sleeps():
        time.sleep(30)


    @pytest.fixture
    def slow():
        time.sleep(30)


    def test_waits_for_its_fixture(slow):
        pass


    @pytest.fixture
    def afterwards():
        yield                   # torn down once the call is over
        assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
        assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL


    def test_inside_the_limit(afterwards):
        left, _ = signal.getitimer(signal.ITIMER_REAL)
        assert 0.0 < left <= 0.25
    """


@pytest.fixture(scope="module")
def inner(tmp_path_factory):
    where = tmp_path_factory.mktemp("limit")
    (where / "conftest.py").write_text(textwrap.dedent(INNER_CONFTEST))
    (where / "test_inner.py").write_text(textwrap.dedent(INNER_CASES))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "test_inner.py", "-v", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=where, capture_output=True, text=True, timeout=120)


def test_a_case_that_hangs_fails_by_its_name_and_the_run_goes_on(inner):
    out = inner.stdout
    assert inner.returncode == 1, out + inner.stderr
    assert "test_inner.py::test_sleeps hung: its call took more than 0.25 s" in out
    assert ("test_inner.py::test_waits_for_its_fixture hung: its set-up took "
            "more than 0.25 s") in out
    # pytest calls a failed set-up an error; the case after them still ran
    assert "1 failed, 1 passed, 1 error" in out


def test_a_case_inside_the_limit_is_untouched_and_the_timer_is_off_after_it(inner):
    assert "test_inner.py::test_inside_the_limit PASSED" in inner.stdout
    assert "at teardown of" not in inner.stdout     # the fixture's own checks held
