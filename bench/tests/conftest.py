import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import os  # noqa: E402

# CPU compilations stay out of the checkout's chip cache (.jax_cache)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(BENCH.parent / ".bench_out" / "jax_cache_cpu"))
