"""Record the small CPU trace that ``test_tracing.py`` reduces.

    JAX_PLATFORMS=cpu python3 perfbench/tests/record_trace.py

A window span holding six calls of one jitted function, each followed by a
``bench.wait`` span of growing length in which the device is idle.  The
checkout's path, which the Python tracer records, is overwritten by a
placeholder of the same length, so the file does not depend on where it
was recorded.
"""

import glob
import os
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def main() -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(6):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.01 * (i + 1))
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    root = str(OUT.parents[3]).encode() + b"/"
    blob = Path(src).read_bytes().replace(root, b"/" + b"x" * (len(root) - 2) + b"/")
    OUT.parent.mkdir(exist_ok=True)
    OUT.write_bytes(blob)
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
