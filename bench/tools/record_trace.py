"""Record a small profiler trace on the chip, for the trace-reduction test.

    python bench/tools/record_trace.py OUT_DIR

Runs a matmul, the paged-attention kernel and the bucketed WASH shuffle
kernel under the benchmark's host annotations, with idle gaps between
them, and writes the trace under ``OUT_DIR``.  It prints the planes, lines
and a few events of each line, so that a reader of the trace format can
see how the device and host planes are named.
"""

from __future__ import annotations

import glob
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.kernels.wash_shuffle import bucketed_shuffle_pallas

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: JAX found no TPU")
    k = jax.random.key(0)
    a = jax.random.normal(k, (2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda x: x @ x)
    B, H, KV, hd, P, ps, mp = 4, 32, 8, 128, 64, 16, 8
    q = jax.random.normal(k, (B, H, hd), jnp.bfloat16)
    kp = jax.random.normal(k, (P, ps, KV, hd), jnp.bfloat16)
    table = (jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp) % (P - 1)) + 1
    lengths = jnp.array([5, 40, 77, 128], jnp.int32)
    attend = jax.jit(paged_attention_pallas)
    x = jax.random.normal(k, (2, 1 << 20), jnp.bfloat16)
    idx = jnp.arange(2 * 4096, dtype=jnp.int32).reshape(2, 4096) * 97
    shuffle = jax.jit(bucketed_shuffle_pallas)
    for f, args in ((mm, (a,)), (attend, (q, kp, kp, table, lengths)),
                    (shuffle, (x, idx))):
        jax.block_until_ready(f(*args))

    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.matmul"):
                jax.block_until_ready(mm(a))
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                jax.block_until_ready(attend(q, kp, kp, table, lengths))
            with jax.profiler.TraceAnnotation("bench.shuffle"):
                jax.block_until_ready(shuffle(x, idx))
    jax.profiler.stop_trace()

    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    print("trace", path, os.path.getsize(path), "bytes")
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k_, str(v)[:80]) for k_, v in e.stats])


if __name__ == "__main__":
    main(sys.argv[1])
