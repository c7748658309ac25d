"""Record ``data/scoped_v5e.xplane.pb`` on one chip: a program with
two ``qsparse.*`` scopes and one unscoped reduction, kept apart by
optimization barriers, run twice around a 20 ms host sleep under a
``bench.wait`` span.

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


@jax.jit
def program(x):
    # the barriers keep XLA from fusing the stages into one operation,
    # which would carry the scope of its root alone
    with jax.named_scope("qsparse.grad"):
        y = jax.lax.optimization_barrier(jnp.tanh(x @ x))
    with jax.named_scope("qsparse.compress"):
        kept = jax.lax.optimization_barrier(
            jnp.where(jnp.abs(y) > 0.5, y, 0.0))
    return kept, jnp.sum(kept)


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record on a TPU")
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048))
    jax.block_until_ready(program(x))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        jax.block_until_ready(program(x))
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.02)
        jax.block_until_ready(program(x))
        jax.profiler.stop_trace()
        src, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        shutil.copyfile(src, out)


if __name__ == "__main__":
    main(sys.argv[1])
