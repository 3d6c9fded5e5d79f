"""The benchmark's tests import ``bench`` from the repository's root."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench_cells import no_compile_cache, root  # noqa: E402,F401  (fixtures)
