"""`pytest benchmarks/selfcheck` runs on the CPU, by hand; it is not part of
the repo's tests/."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
