"""Collection guard: fail fast, with an actionable message, before pytest
prints 10 modules' worth of identical ImportError tracebacks.

The root ``conftest.py`` bootstraps ``sys.path``; this file verifies the
environment actually works (repro importable, jax and hypothesis present)
and aborts collection with one clear diagnostic when it doesn't.

It also turns on strict JAX numerics for the whole suite: implicit rank
promotion (``(4,) + (2, 4)``-style broadcasts) is the classic source of
silently wrong attention masks, so tier-1 runs with
``jax_numpy_rank_promotion="raise"`` — shape intent must be written out.
"""

import pytest


def _guard() -> None:
    problems = []
    try:
        import repro  # noqa: F401
    except ImportError as e:
        problems.append(
            f"cannot import 'repro' ({e}); run tests from the repo root "
            f"(root conftest.py adds src/ to sys.path) or set "
            f"PYTHONPATH=src")
    try:
        import jax  # noqa: F401
    except ImportError as e:
        problems.append(f"jax is required for the test suite: {e}")
    try:
        from hypothesis import given, settings, strategies  # noqa: F401
    except ImportError as e:
        problems.append(f"hypothesis is required for the test suite: {e}")
    if problems:
        raise pytest.UsageError(
            "test environment broken:\n  - " + "\n  - ".join(problems))


def _strict_jax() -> None:
    import jax
    jax.config.update("jax_numpy_rank_promotion", "raise")


_guard()
_strict_jax()
