"""One run of one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and,
with ``--trace 1``, ``breakdown``), and as the last lines of standard
error each number compared with the reference beside its limit. Exits
2, printing no result, when the first device is not a TPU or there are
fewer chips than the cell asks for. See ``perfbench/README.md``.
"""
import os
import pathlib
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]
# the persistent compile cache lives at one fixed path in the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")

import jax  # noqa: E402

from perfbench import harness  # noqa: E402
from repro import compile_cache  # noqa: E402

if __name__ == "__main__":
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.exit(harness.main(t_start=T_START))
