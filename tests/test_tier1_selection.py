"""Tier-1 (``-m "not slow"``) is the same set of tests on every day.

Until PR 46 a hook in ``tests/conftest.py`` took the ``slow`` mark off a
subset of the slow gates chosen by a hash of the date, so the count of
passes the driver holds a PR to moved with the calendar (two gates on one
day, twelve on another). This collects some of the files that carry slow
gates under two different dates and holds the two selections equal, and
apart from what ``-m slow`` selects."""

import os
import subprocess
import sys
import textwrap

_FILES = [
    "tests/test_rl.py",
    "tests/test_rl_extras.py",
    "tests/test_multi_agent.py",
    "tests/test_tuned_examples.py",
    "tests/test_rl_connectors_ope.py",
    "tests/test_serve.py",
]

# a plugin that makes ``datetime.date.today()`` the day ``RT_TEST_TODAY`` names
_FAKE_TODAY = textwrap.dedent("""
    import datetime
    import os


    class date(datetime.date):
        @classmethod
        def today(cls):
            return cls.fromisoformat(os.environ["RT_TEST_TODAY"])


    datetime.date = date
""")


def _collect(marker, today, plugin_dir):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", RT_TEST_TODAY=today,
               PYTHONPATH=os.pathsep.join(
                   [plugin_dir, os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-m", marker, "-p", "no:cacheprovider", "-p", "fake_today", *_FILES],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return sorted(ln for ln in proc.stdout.splitlines() if "::" in ln)


def test_the_selection_is_not_the_dates(tmp_path):
    with open(tmp_path / "fake_today.py", "w") as f:
        f.write(_FAKE_TODAY)
    # under the hash of the old hook 2026-09-30 chose twelve gates of
    # twenty and 2026-10-03 two
    one = _collect("not slow", "2026-09-30", str(tmp_path))
    other = _collect("not slow", "2026-10-03", str(tmp_path))
    slow = _collect("slow", "2026-09-30", str(tmp_path))
    assert one == other
    assert len(slow) >= 6 and not set(slow) & set(one), set(slow) & set(one)
