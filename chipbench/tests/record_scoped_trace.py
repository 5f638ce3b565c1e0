"""Record the scoped trace that ``test_scopes.py`` reads.

    python chipbench/tests/record_scoped_trace.py chipbench/tests/data

Runs on a TPU: the Mamba2 smoke configuration's safeguard step (m = 4,
1 ``sign_flip``, ``safeguard_double`` with the Pallas Gram kernel, windows
short enough that both accumulators reset inside the trace), built by the
training CLI's wiring and driven through ``Trainer.run`` with the
benchmark's feed and step wrapper, so the trace holds the program's named
scopes and ``repro.*`` spans with the ``chipbench.*`` spans inside them.
Writes ``scoped.xplane.pb`` and the compiled step's HLO text,
``scoped.hlo.txt.gz``, into the directory given.
"""

import glob
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax                                                  # noqa: E402

from chipbench import run as runner                         # noqa: E402
from repro import configs as C                              # noqa: E402
from repro.launch import train as train_lib                 # noqa: E402

M, SEQ, STEPS, LOG_EVERY = 4, 256, 6, 3
ARGS = ["--workers", str(M), "--byz", "1", "--batch", str(M), "--seq",
        str(SEQ), "--attack", "sign_flip", "--defense", "safeguard_double",
        "--t0", "2", "--t1", "4", "--floor", "0.01", "--lr", "0.005",
        "--log-every", str(LOG_EVERY)]


def main(out: str):
    annotate = jax.profiler.TraceAnnotation
    trainer = train_lib.build_trainer(C.get_smoke("mamba2-130m"),
                                      train_lib.parse_args(ARGS))
    pool = [next(trainer.data_iter) for _ in range(4)]
    trainer.data_iter = runner.Feed(pool, annotate)
    stepper = runner.Stepper(trainer.step_fn, annotate)
    trainer.step_fn = stepper
    stepper.sync_in = LOG_EVERY
    trainer.run(LOG_EVERY, verbose=False)             # compiles the step
    hlo = stepper.fn.lower(trainer.state, pool[0]).compile().as_text()
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with annotate("chipbench.window"):
            for _ in range(STEPS // LOG_EVERY):
                stepper.sync_in = LOG_EVERY
                trainer.run(LOG_EVERY, verbose=False)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0],
                    Path(out) / "scoped.xplane.pb")
    with gzip.open(Path(out) / "scoped.hlo.txt.gz", "wt") as f:
        f.write(hlo)


if __name__ == "__main__":
    main(sys.argv[1])
