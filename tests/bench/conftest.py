"""The benchmark's tests import it from the checkout root, run after the
rest of the suite, and leave no sharding mesh behind."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_collection_modifyitems(config, items):
    """Keep the order the rest of the suite had before these tests: they
    come last (test files share process-wide state, such as the current
    sharding mesh, with whatever ran before them on a worker)."""
    mine = {i for i in items if str(i.path).startswith(HERE + os.sep)}
    items[:] = ([i for i in items if i not in mine]
                + [i for i in items if i in mine])


@pytest.fixture(autouse=True)
def _no_mesh_left_behind():
    yield
    from repro.parallel import sharding
    sharding.set_current_mesh(None)
