"""The suite's own guard (tests/conftest.py): a limit a test."""

import os
import subprocess
import sys
import textwrap

import conftest

HERE = os.path.dirname(os.path.abspath(__file__))


def test_a_test_past_its_limit_fails_by_name_and_the_run_goes_on(tmp_path):
    """A run of two tests under conftest's limit patched to 0.3 s: the
    one that sleeps 5 s fails with its name in the message, the one after
    it passes."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "suite_conftest", {os.path.join(HERE, "conftest.py")!r})
        suite = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(suite)
        suite.TEST_LIMIT_S = 0.3
        _limit_each_test = suite._limit_each_test
    """))
    (tmp_path / "test_two.py").write_text(textwrap.dedent("""
        import time

        def test_sleeps_past_the_limit():
            time.sleep(5)

        def test_the_one_after_it():
            pass
    """))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=tmp_path)
    assert run.returncode == 1, run.stdout + run.stderr
    assert "1 failed, 1 passed" in run.stdout, run.stdout
    assert ("test_two.py::test_sleeps_past_the_limit passed its limit of "
            "0.3 s") in run.stdout, run.stdout


def test_every_test_runs_under_the_limit(request):
    """This one too: the alarm is armed, for the suite's 120 s."""
    import signal
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.TEST_LIMIT_S == 120.0
    assert "_limit_each_test" in request.fixturenames
