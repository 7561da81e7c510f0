"""Optional-`hypothesis` shim for the test suite.

`hypothesis` is a dev extra (see pyproject.toml), not a runtime dep.  When
it is installed, this module re-exports the real ``given``/``settings``/
``example``/``strategies``.  When it is not, property-based tests are
collected as skips (instead of the whole module failing to import) and
every plain test in the same file still runs.
"""
from __future__ import annotations

import pytest

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        def deco(fn):
            @pytest.mark.skip(reason="hypothesis not installed")
            def stub():
                pass
            stub.__name__ = fn.__name__
            stub.__doc__ = fn.__doc__
            return stub
        return deco

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    example = settings

    class _AnyStrategy:
        """Stand-in for ``hypothesis.strategies``: every attribute is a
        callable returning None (only ever passed to the stub ``given``)."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

__all__ = ["example", "given", "settings", "st", "HAVE_HYPOTHESIS"]
