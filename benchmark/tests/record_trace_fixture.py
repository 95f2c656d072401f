"""Record the small trace that test_trace_reduce.py reads (run on the chip).

    python3 benchmark/tests/record_trace_fixture.py <out_dir>

Inside one `bench.window` annotation: three launches of one jitted program
(`jit_work`), each under `bench.launch` and waited for, and between them a
50 ms host sleep under `bench.host_wait`. So the device is busy three
times, and its two longest idle gaps fall under `bench.host_wait`.
"""
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def work(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.float32)
    work(x).block_until_ready()  # compile outside the trace
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.launch"):
                work(x).block_until_ready()
            if i < 2:
                with jax.profiler.TraceAnnotation("bench.host_wait"):
                    time.sleep(0.05)
    jax.profiler.stop_trace()
    print(jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
