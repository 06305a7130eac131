"""Records ``cpu.xplane.pb``, the small CPU trace the reduction is
tested on: a ``window`` span holding three ``decode`` spans (a jitted
matmul) and three ``page`` spans (an elementwise op), with host sleeps
between them that leave the device idle.

    cd bench/tests/data && JAX_PLATFORMS=cpu python record_trace.py
"""
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main():
    # op metadata keeps bare file names, no path of the recording machine
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    step = jax.jit(lambda x: jnp.tanh(x @ x))

    def page(x):
        return (x + 1).block_until_ready()

    x = jnp.ones((512, 512))
    step(x).block_until_ready()
    page(x)
    tmp = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # keeps source paths out of the file
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("decode"):
                step(x).block_until_ready()
            time.sleep(0.02)
            with jax.profiler.TraceAnnotation("page"):
                page(x)
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = next(tmp.rglob("*.xplane.pb"))
    shutil.copy(src, Path(__file__).with_name("cpu.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
