"""Record the small trace ``small.xplane.pb`` that ``test_trace.py`` reads.

Run on the chip: ``python3 bench/tests/data/record_trace.py <out dir>``. Three
steps of a small jitted matmul chain inside a ``bench.window`` span; after each
step the ``bench.step_hook`` span sleeps 20 ms, so the device idles there.
"""
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    f(x).block_until_ready()
    tmp = Path(out) / "raw"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
            with TraceAnnotation("bench.step_hook"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    (src,) = tmp.rglob("*.xplane.pb")
    shutil.copy(src, Path(out) / "small.xplane.pb")
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
