"""Guard on how the suite spends its time limit: a hung child process
fails its test instead of skipping."""
import ast
import glob
import os

TESTS = os.path.dirname(os.path.abspath(__file__))


def timeout_skips(src, path="<src>"):
    """`path:line` of every `skip(...)` call inside an
    `except ... TimeoutExpired` handler of `src`."""
    found = []
    for node in ast.walk(ast.parse(src, filename=path)):
        if not (isinstance(node, ast.ExceptHandler) and node.type is not None
                and "TimeoutExpired" in ast.unparse(node.type)):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                fn = sub.func
                name = fn.attr if isinstance(fn, ast.Attribute) else \
                    getattr(fn, "id", "")
                if name == "skip":
                    found.append(f"{path}:{sub.lineno}")
    return found


def test_timeout_skip_check_flags_a_skip():
    src = ("import subprocess, pytest\n"
           "try:\n"
           "    subprocess.run(['true'], timeout=1)\n"
           "except (OSError, subprocess.TimeoutExpired):\n"
           "    pytest.skip('slow box')\n"
           "except ValueError:\n"
           "    pytest.skip('not a timeout')\n")
    assert timeout_skips(src) == ["<src>:5"]


def test_no_subprocess_suite_skips_on_timeout():
    """A child that hangs must fail its test, with its stderr tail, not
    read as a skip: every test module and helper that catches
    `TimeoutExpired` is checked, and the subprocess suites do catch it
    (through tests/_subproc.py)."""
    paths = sorted(glob.glob(os.path.join(TESTS, "*.py")))
    found, catching = [], []
    for path in paths:
        with open(path) as f:
            src = f.read()
        rel = os.path.relpath(path, os.path.dirname(TESTS))
        found += timeout_skips(src, rel)
        if "TimeoutExpired" in src:
            catching.append(os.path.basename(path))
    assert found == [], found
    assert "_subproc.py" in catching
    for suite in ("test_sharded_multidevice.py", "test_dryrun_small.py"):
        with open(os.path.join(TESTS, suite)) as f:
            assert "from _subproc import run_script" in f.read(), suite

