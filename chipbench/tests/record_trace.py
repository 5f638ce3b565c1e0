"""Record the small trace that ``test_trace_reduce.py`` reads.

    python chipbench/tests/record_trace.py chipbench/tests/data/small.xplane.pb

Runs on a TPU: five rounds of the benchmark's host spans around a Pallas
Gram kernel and a small jitted program, with a 3 ms host pause inside each
round's ``chipbench.batch`` span, so the device idles in a gap the
reduction must attribute to it.
"""

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src")]

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.kernels.safeguard_filter import pairwise_sqdist  # noqa: E402

ROUNDS, PAUSE_S = 5, 0.003


def main(out: str):
    annotate = jax.profiler.TraceAnnotation
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8 * 32768), jnp.float32)
    other = jax.jit(lambda a: jnp.tanh(a).sum())
    jax.block_until_ready((pairwise_sqdist(x, block_d=None, interpret=False),
                           other(x)))
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with annotate("chipbench.window"):
            for _ in range(ROUNDS):
                with annotate("chipbench.batch"):
                    time.sleep(PAUSE_S)
                with annotate("chipbench.dispatch"):
                    out_k = pairwise_sqdist(x, block_d=None, interpret=False)
                    out_o = other(x)
                with annotate("chipbench.block"):
                    jax.block_until_ready((out_k, out_o))
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0],
                    out)


if __name__ == "__main__":
    main(sys.argv[1])
