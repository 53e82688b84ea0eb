"""Self-checks of the yardstick: ``python -m pytest chipbench/tests -q``.
Not part of the repo's tier-1 tests. They run on the CPU."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
