"""Records ``data/tiny.xplane.pb``: a few steps of a small jitted program
on the chip, inside ``bench.window`` / ``bench.step`` spans, with a host
pause between steps so that there are idle gaps to attribute. Run once on
the chip (``python benchmark/tests/record_tiny_trace.py <out-file>``); the
test ``test_reduce_trace.py::test_recorded_chip_trace`` reads the result.
"""
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(out_path: str) -> None:
    import jax
    import jax.numpy as jnp
    import reduce_trace
    from run import Spans

    @jax.jit
    def step(x):
        def body(_, v):
            return jnp.tanh(v @ v.T) @ v
        return jax.lax.fori_loop(0, 3, body, x)

    x = jnp.ones((512, 512), jnp.float32)
    step(x).block_until_ready()
    spans = Spans(tracing=True)
    trace_dir = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with spans("window"):
        for _ in range(3):
            with spans("step"):
                x = step(x)
                x.block_until_ready()
            with spans("pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(reduce_trace.find_xplane(trace_dir), out_path)
    shutil.rmtree(trace_dir)
    print(os.path.getsize(out_path), "bytes;", reduce_trace.reduce(out_path))


if __name__ == "__main__":
    main(sys.argv[1])
