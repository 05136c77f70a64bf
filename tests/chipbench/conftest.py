import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), os.path.join(ROOT, "chipbench")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _plain_run_windows(self, windows, agg):
    for w in windows:
        yield self.run_window(w, agg)


@pytest.fixture
def run_windows():
    """``HybridExecutor.run_windows`` as the program has it, or where it
    has none the plain loop over ``run_window`` that the call stands
    for; a fault or a recorder wraps it."""
    from repro.pipeline import queries
    return getattr(queries.HybridExecutor, "run_windows",
                   _plain_run_windows)


@pytest.fixture
def slide_one_altered(run_windows):
    """``slide_one_altered(factor)``: a ``run_windows`` that multiplies
    the last window of each slide by ``factor``."""
    def make(factor):
        def f(self, windows, agg):
            last = len(windows) - 1
            for j, v in enumerate(run_windows(self, windows, agg)):
                yield v * factor if j == last else v
        return f
    return make
