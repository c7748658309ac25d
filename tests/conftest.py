"""Shared fixtures.  NOTE: no XLA_FLAGS device-count forcing here —
smoke tests must see the real single CPU device; multi-device tests
spawn subprocesses that set the flag before importing jax.

When ``hypothesis`` is not installed, a minimal stub is injected into
``sys.modules`` so the property-test modules still import; every
``@given``-decorated test is then collected as a single skipped test
with an explicit reason instead of erroring at collection time.
"""

import os
import platform
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# The parity tests compare two compiled programs of the same math bit for
# bit.  On an x86 host with AVX-512, XLA's CPU backend vectorizes such
# programs differently and their f32 results part in the last bits, so
# the tests pin its code generation to AVX2, which every x86-64 host
# this suite runs on has (jax is imported after this file).
CPU_XLA_FLAGS = ("--xla_cpu_max_isa=AVX2"
                 if platform.machine() in ("x86_64", "AMD64") else "")
if CPU_XLA_FLAGS and CPU_XLA_FLAGS not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + CPU_XLA_FLAGS).strip()

try:
    import hypothesis  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    class _Strategy:
        """Inert stand-in: absorbs .map/.filter/.flatmap chains and —
        for ``@st.composite``-built strategies, which the stub turns
        into _Strategy instances — calls."""

        def map(self, _fn):
            return self

        def filter(self, _fn):
            return self

        def flatmap(self, _fn):
            return self

        def __call__(self, *_a, **_k):
            return self

    class _Strategies(types.ModuleType):
        def __getattr__(self, _name):
            return lambda *a, **k: _Strategy()

    def _given(*_a, **_k):
        def deco(fn):
            @pytest.mark.skip(
                reason="hypothesis not installed; property test skipped"
            )
            def shim():
                pass

            shim.__name__ = fn.__name__
            shim.__qualname__ = getattr(fn, "__qualname__", fn.__name__)
            shim.__doc__ = fn.__doc__
            shim.__module__ = fn.__module__
            return shim

        return deco

    def _settings(*_a, **_k):
        # usable both as @settings(...) and as settings(...)(fn)
        return lambda fn: fn

    _st = _Strategies("hypothesis.strategies")
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.assume = lambda *_a, **_k: True
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st

if HAVE_HYPOTHESIS:
    # example budgets: "fleet" keeps the R>=256 scenario lane cheap
    # (pytest -m scenarios in CI); select with HYPOTHESIS_PROFILE=
    from hypothesis import settings as _hs

    _hs.register_profile("fleet", max_examples=5, deadline=None)
    _hs.register_profile("ci", max_examples=25, deadline=None)
    _hs.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))


def run_subprocess(code: str, devices: int = 8, timeout: int = 900):
    """Run python code in a subprocess with a forced device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        f"{CPU_XLA_FLAGS}").strip()
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed\nSTDOUT:\n{out.stdout[-4000:]}\n"
            f"STDERR:\n{out.stderr[-4000:]}"
        )
    return out.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess
