"""The suite guards its own clock: the limit of ``tests/conftest.py``
fails a test that hangs and lets the run go on, and no test waits
longer than that limit for a whole child run."""
import ast
import glob
import importlib.util
import os
import subprocess
import sys
import textwrap

_TESTS = os.path.dirname(os.path.abspath(__file__))


def load_suite_conftest():
    """``tests/conftest.py`` under a name of its own: ``conftest`` in
    ``sys.modules`` is whichever conftest.py pytest imported last."""
    spec = importlib.util.spec_from_file_location(
        "suite_conftest", os.path.join(_TESTS, "conftest.py"))
    suite = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(suite)
    return suite


def test_a_test_past_the_limit_fails_and_the_run_goes_on(tmp_path):
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {_TESTS!r})
        from test_suite_limits import load_suite_conftest
        suite = load_suite_conftest()
        suite.TEST_LIMIT_S = 1
        pytest_runtest_call = suite.pytest_runtest_call
        """))
    (tmp_path / "test_hang.py").write_text(textwrap.dedent("""\
        import time
        def test_hangs(): time.sleep(30)
        def test_next(): pass
        """))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        capture_output=True, text=True, timeout=60, cwd=tmp_path)
    out = r.stdout + r.stderr
    assert r.returncode == 1, out[-3000:]
    assert "1 failed, 1 passed" in out, out[-3000:]
    assert "test_hangs passed the suite's limit of 1 s" in out, out[-3000:]
    # the watchdog's dump of the stack the test hung in
    assert "Timeout (0:00:01)!" in out, out[-3000:]
    assert "in test_hangs" in out, out[-3000:]


def _literal_timeouts(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "timeout" \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, (int, float)):
                    yield node.lineno, kw.value.value


def test_no_test_waits_longer_than_the_limit():
    """A ``timeout=`` of a child run above the limit can never be
    reached: the test that passes it is failed first."""
    limit = load_suite_conftest().TEST_LIMIT_S
    over = [f"{os.path.basename(path)}:{line} timeout={value}"
            for path in sorted(glob.glob(os.path.join(_TESTS, "*.py")))
            for line, value in _literal_timeouts(path)
            if value > limit]
    assert over == []
