#!/usr/bin/env python3
"""Record the small trace that ``test_perfbench_trace.py`` reads.

    python perfbench/tests/make_trace_fixture.py perfbench/tests/fixtures

On the chip: the program's fused CIM matmul kernel at a small shape, run
three times inside the harness's traced-window annotation with a host
sleep (annotated ``fixture.host_wait``) after each call, so the trace
holds kernel events, busy time and idle gaps with a known host label.
Writes ``cim_matmul.xplane.pb`` (well under 1 MB) and prints its
reduction.
"""
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from perfbench import trace
    from repro.kernels.cim_matmul import cim_matmul_pallas
    key = jax.random.PRNGKey(0)
    a = jax.random.randint(key, (256, 4, 128), -8, 8).astype(jnp.float32)
    d = jax.random.randint(key, (2, 4, 128, 256), -3, 4).astype(jnp.int8)
    s_p = jnp.full((2, 4, 256), 8.0)
    deq = jnp.full((2, 4, 256), 0.01)
    f = jax.jit(lambda a: cim_matmul_pallas(a, d, s_p, deq, psum_bits=6))
    f(a).block_until_ready()
    tmp = os.path.join(out_dir, "_raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("fixture.host_wait"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "cim_matmul.xplane.pb")
    shutil.copy(trace.newest_xplane(tmp), dst)
    shutil.rmtree(tmp)
    print(trace.describe(dst))
    print(os.path.getsize(dst), "bytes:", trace.load(dst))


if __name__ == "__main__":
    main(sys.argv[1])
