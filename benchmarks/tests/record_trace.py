"""How benchmarks/testdata/small.xplane.pb was recorded (on a TPU v5e,
through the chip tool): three runs of a jitted program named scan_steps
with a 30 ms host span named pass_begin before each, inside a span named
bench_window. test_trace_reduce.py reduces the file on the CPU.

    python benchmarks/tests/record_trace.py <out.xplane.pb>
"""

import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def scan_steps(x):
    def body(c, _):
        return jnp.tanh(c @ c) * 0.5, ()
    return jax.lax.scan(body, x, None, length=8)[0]


def main(out: str) -> None:
    sys.path.insert(0, __file__.rsplit("/benchmarks/", 1)[0] + "/benchmarks")
    from harness import trace_reduce as tr
    f = jax.jit(scan_steps)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pass_begin"):
                time.sleep(0.03)
            x = f(x)
            x.block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(tr.find_xplane(tmp), out)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
