import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture(scope="session")
def bench():
    """BENCHMARK.json with the pending cells added, so that tests drive
    them as the harness will once they are in it."""
    from chipbench.calibrate import with_pending

    return with_pending()
