#!/usr/bin/env python3
"""Record the tiny trace that tests/test_tracereduce.py checks the reduction
on: `python3 benchmark/tests/make_trace.py <out_dir>` on the machine with the
chip (or here, on the CPU). One jitted program with a sort and a matrix
product (and, on several devices, a psum) runs three times inside a
`bench/frame` annotation with a host sleep of 20 ms between the calls, so the
trace holds known idle gaps inside the frame."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1]
n = len(jax.devices())
x = jnp.ones((n, 512, 512), jnp.float32)


def step(a):
    b = jnp.sort(a @ a, axis=-1)
    return jax.lax.psum(b.sum(), "i") if n > 1 else b.sum()


f = jax.pmap(step, axis_name="i") if n > 1 else jax.jit(lambda a: step(a[0]))
jax.block_until_ready(f(x))
tmp = os.path.join(out, "_tmp")
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(tmp, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench/between"):
    time.sleep(0.005)
with jax.profiler.TraceAnnotation("bench/frame"):
    for _ in range(3):
        jax.block_until_ready(f(x))
        time.sleep(0.02)
with jax.profiler.TraceAnnotation("bench/between"):
    time.sleep(0.005)
jax.profiler.stop_trace()
src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
kind = jax.devices()[0].platform
dst = os.path.join(out, f"tiny_{kind}_{n}dev.xplane.pb")
shutil.copy(src, dst)
shutil.rmtree(tmp)
print(dst, os.path.getsize(dst))
