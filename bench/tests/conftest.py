"""What every benchmark test leaves behind for the next.

``control.serving`` builds one engine and keeps it, in its default
argument, for every seed it reads: one process reads one cell there.  A
test run reads the controls of several cells in one process, so each test
ends by dropping that engine; else the next cell's control would be
served by the last cell's model.
"""
import sys

import pytest


@pytest.fixture(autouse=True)
def no_engine_outlives_its_cell():
    yield
    control = sys.modules.get("control")
    if control is not None:
        control.serving.__defaults__[-1].clear()
