"""Records ``spans.xplane.pb``, the small CPU trace the attribution of
``bench/spans.py`` is tested on: a ``window`` span holding two
``engine.step`` spans with the program's span names nested as the
engine nests them, and host sleeps that leave the device idle:

- ``engine.step`` > ``engine.admit`` > ``engine.prefill`` (a jitted
  matmul) and ``engine.slot_write`` (an elementwise op);
- ``engine.step`` > ``engine.decode`` (the matmul);
- ``engine.step`` > ``engine.page`` > ``kv.encode`` (an op, then a
  10 ms sleep), ``pool.put`` (a 15 ms sleep), ``kv.decode`` (a program
  compiled anew), ``kv.restore`` (an op);
- a 20 ms sleep inside the window with no program span open.

    cd bench/tests/data && JAX_PLATFORMS=cpu python record_spans.py
"""
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main():
    # op metadata keeps bare file names, no path of the recording machine
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    matmul = jax.jit(lambda x: jnp.tanh(x @ x))
    bump = jax.jit(lambda x: x + 1)
    x = jnp.ones((512, 512))
    matmul(x).block_until_ready()
    bump(x).block_until_ready()
    tmp = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # keeps source paths out of the file
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with TraceAnnotation("window"):
        for step in (1, 2):
            with TraceAnnotation("engine.step", step=step):
                with TraceAnnotation("engine.admit", rid=f"r{step}", prompt_len=512):
                    with TraceAnnotation("engine.prefill", rid=f"r{step}", tokens=512):
                        matmul(x).block_until_ready()
                    with TraceAnnotation("engine.slot_write", rid=f"r{step}"):
                        bump(x).block_until_ready()
                with TraceAnnotation("engine.decode", active=1):
                    matmul(x).block_until_ready()
                with TraceAnnotation("engine.page", rid=f"r{step}", start=0):
                    with TraceAnnotation("kv.encode", layer="l0"):
                        bump(x).block_until_ready()
                        time.sleep(0.01)
                    with TraceAnnotation("pool.put", layer="l0"):
                        time.sleep(0.015)
                    with TraceAnnotation("kv.decode", layer="l0"):
                        # a new function each step: traced, lowered, compiled
                        jax.jit(lambda v, s=step: v * s - 2)(x).block_until_ready()
                    with TraceAnnotation("kv.restore", layer="l0"):
                        bump(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = next(tmp.rglob("*.xplane.pb"))
    shutil.copy(src, Path(__file__).with_name("spans.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
