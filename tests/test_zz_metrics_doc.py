"""Metrics-doc lint as a tier-1 gate: every registered rt_* series must be
unique and documented in README's metrics table (scripts/check_metrics.py).
Named ``test_zz_*`` so it sorts late in the suite."""

import importlib.util
import os


def _load_checker():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "scripts", "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_all_registered_metrics_documented():
    cm = _load_checker()
    problems = cm.check()
    assert not problems, "metrics-doc lint failed:\n" + "\n".join(
        f"  - {p}" for p in problems)


def test_scanner_sees_known_series():
    """The regex scanner must keep matching the registration idiom — if it
    silently matched nothing, the lint above would pass vacuously."""
    cm = _load_checker()
    regs = cm.registered_metrics()
    for name in ("rt_task_queue_wait_seconds", "rt_object_store_bytes",
                 "rt_oom_kills_total", "rt_train_launch_gap_seconds",
                 "rt_hbm_used_bytes", "rt_nodes"):
        assert name in regs, f"scanner lost {name}"
