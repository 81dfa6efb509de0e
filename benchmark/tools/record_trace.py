"""Records the small trace that benchmark/tests keeps: one tiny causal
flash attention (forward and gradients) and a matrix product, three times,
under the harness's window and spans, with an idle pause between steps.
Run on the chip:  python3 -m benchmark.tools.record_trace <out dir>"""

import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmark.spans import Spans
from benchmark.trace import find_xplane


def main(out_dir: str) -> None:
    from ray_tpu.ops.attention import flash_attention

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: a trace of the chip needs the chip")
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (1, 512, 2, 128), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    a = jax.random.normal(key, (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(q, k, v, a):
        grads = jax.grad(lambda q, k, v: flash_attention(
            q, k, v, True).astype(jnp.float32).sum(), argnums=(0, 1, 2))(
                q, k, v)
        return grads, a @ a

    jax.block_until_ready(step(q, k, v, a))
    spans = Spans()
    trace_dir = tempfile.mkdtemp(prefix="record_trace_")
    jax.profiler.start_trace(trace_dir)
    spans.annotate = True
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with spans.span("step"):
                jax.block_until_ready(step(q, k, v, a))
            with spans.span("pause"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    dest = os.path.join(out_dir, "tiny_tpu.xplane.pb")
    shutil.copy(find_xplane(trace_dir), dest)
    print("wrote", dest, os.path.getsize(dest), "bytes")
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
