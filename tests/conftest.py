"""Shared test configuration.

The service tier is pure-Python asyncio; tests run against the in-memory
fake coordination store (the reference's biggest testability gap — it has
integration-only tests against a live ZooKeeper, SURVEY §4).

JAX env pinning: tests run on the CPU wherever they run.  Any test that
imports jax (only ``__graft_entry__.py`` does) sees the CPU platform with
eight virtual devices; the chip is ``chip_smoke.py``'s business alone.

Native build (ROADMAP D11): ``native/`` is built here, once, before any
test module is imported, and a failed build fails the session.  The C
lanes serve nearly every query; on a tree where they went unbuilt their
suites would skip and the session would still report green.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _flag
    ).strip()

import subprocess
import sys
import sysconfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import logging

import pytest


def _build_native() -> None:
    try:
        proc = subprocess.run(
            ["make", "-j", str(os.cpu_count() or 1), "-C",
             os.path.join(ROOT, "native")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        out, rc = proc.stdout, proc.returncode
    except OSError as e:
        out, rc = str(e), -1
    ext = os.path.join(ROOT, "binder_tpu", "_binderfastio"
                       + sysconfig.get_config_var("EXT_SUFFIX"))
    if rc != 0 or not os.path.exists(ext):
        pytest.exit("tests/conftest.py: `make -C native` did not build "
                    f"{os.path.relpath(ext, ROOT)} (exit {rc}); the "
                    "native lanes would go untested:\n" + out[-4000:],
                    returncode=2)


# xdist workers import this file too; the controller already built
if "PYTEST_XDIST_WORKER" not in os.environ:
    _build_native()


@pytest.fixture(autouse=True)
def _restore_binder_logger_state():
    """Snapshot/restore handler, level, and propagate state for the
    binder logger tree around every test.

    Several tests (log ring, query log, zlogcat) attach handlers or
    adjust levels on the shared "binder"/"binder.server" loggers; a
    leaked handler changes what LATER tests' servers consider "logging
    armed" (e.g. the TCP fastpath gate's log-ring check), which made
    their behavior depend on test ORDER — green alone, red in the full
    run.  Restoring the exact prior state makes every test see the
    logger tree cold."""
    names = [None] + [n for n in logging.Logger.manager.loggerDict
                      if n == "binder" or n.startswith("binder.")]
    saved = {}
    for name in names:
        logger = logging.getLogger(name)
        saved[name] = (list(logger.handlers), logger.level,
                       logger.propagate, logger.disabled)
    yield
    for name, (handlers, level, propagate, disabled) in saved.items():
        logger = logging.getLogger(name)
        logger.handlers[:] = handlers
        logger.setLevel(level)
        logger.propagate = propagate
        logger.disabled = disabled
    # loggers born mid-test keep their objects (they may be cached by
    # the code under test) but must not keep leaked handlers
    for name in logging.Logger.manager.loggerDict:
        if (name not in saved
                and (name == "binder" or name.startswith("binder."))):
            logger = logging.getLogger(name)
            logger.handlers[:] = []
            logger.setLevel(logging.NOTSET)
            logger.propagate = True
