"""Streamed ingest -> device overlap demonstration.

Builds a multi-part Avro dataset several times the ingest bench's size,
then measures BOTH ingest modes in fresh subprocesses (so ru_maxrss is
per-mode). A chip belongs to one process at a time: this parent never
touches a jax backend (it only writes Avro), and the children run one
after the other, each holding the chip alone:

  whole     decode every file into one host dataset, then transfer
  streamed  labeled_batch_streamed: per-file decode with the
            host->device transfer of chunk i-1 in flight while chunk i
            decodes (io/ingest.py)

Reported per mode: ingest+transfer wall (to a solver-ready device
batch), first-solve wall, peak host RSS. The streamed mode's RSS stays
~one chunk; its wall hides transfer behind decode.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

sys.path.insert(0, ".")
from bench import log  # noqa: E402

N_FILES, ROWS_PER_FILE, D = 6, 30_000, 512

_CHILD = r"""
import json, resource, sys, time
sys.path.insert(0, ".")
mode, data_dir = sys.argv[1], sys.argv[2]
from photon_ml_tpu.utils import enable_compilation_cache
enable_compilation_cache()
import numpy as np
import jax.numpy as jnp
from photon_ml_tpu.io.ingest import IngestSource
from photon_ml_tpu.io.vocab import FeatureVocabulary
from photon_ml_tpu.models import (
    GLMTrainingConfig, OptimizerType, TaskType, train_glm,
)
from photon_ml_tpu.ops.objective import RegularizationContext
import os
paths = sorted(
    os.path.join(data_dir, f) for f in os.listdir(data_dir)
    if f.endswith(".avro")
)
vocab = FeatureVocabulary.load(os.path.join(data_dir, "vocab.txt"))
import jax
jnp.zeros((8,)).block_until_ready()  # backend warmup outside timers
src = IngestSource(paths)
t0 = time.perf_counter()
if mode == "streamed":
    batch, _, _ = src.labeled_batch_streamed(vocab, dtype=jnp.float32)
else:
    batch, _, _ = src.labeled_batch(vocab, dtype=jnp.float32)
jax.block_until_ready(batch.features)
ingest_s = time.perf_counter() - t0
t0 = time.perf_counter()
cfg = GLMTrainingConfig(
    task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
    regularization=RegularizationContext("L2"), reg_weights=(1.0,),
    max_iters=10, track_states=False,
)
(tm,) = train_glm(batch, cfg)
np.asarray(tm.result.w)
solve_s = time.perf_counter() - t0
print(json.dumps({
    "mode": mode,
    "ingest_transfer_s": round(ingest_s, 2),
    "first_solve_s": round(solve_s, 2),
    "peak_rss_mb": round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    ),
    "rows": int(batch.labels.shape[0]),
}))
"""


def main():
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.ingest import make_training_example
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

    rng = np.random.default_rng(0)
    data_dir = tempfile.mkdtemp(prefix="pml_stream_")
    nnz = 24  # sparse-ish records; the DENSE matrix is the memory load
    for i in range(N_FILES):
        recs = []
        for _ in range(ROWS_PER_FILE):
            cols = rng.integers(0, D, size=nnz)
            vals = rng.standard_normal(nnz)
            y = float(rng.uniform() < 0.5)
            recs.append(
                make_training_example(
                    label=y,
                    features={
                        (f"f{c}", ""): float(v)
                        for c, v in zip(cols, vals)
                    },
                )
            )
        write_avro_file(
            os.path.join(data_dir, f"part-{i}.avro"),
            TRAINING_EXAMPLE_SCHEMA,
            recs,
            codec="deflate",
        )
    FeatureVocabulary(
        [feature_key(f"f{j}", "") for j in range(D)], add_intercept=False
    ).save(os.path.join(data_dir, "vocab.txt"))
    log(
        f"dataset: {N_FILES} files x {ROWS_PER_FILE} rows, dense d={D} "
        f"({N_FILES * ROWS_PER_FILE * D * 4 / 1e6:.0f} MB f32 total)"
    )
    child = os.path.join(data_dir, "child.py")
    with open(child, "w") as f:
        f.write(_CHILD)
    for mode in ("whole", "streamed"):
        proc = subprocess.run(
            [sys.executable, child, mode, data_dir],
            capture_output=True, text=True, timeout=1500,
            env={
                **os.environ,
                "PYTHONPATH": os.getcwd()
                + ":"
                + os.environ.get("PYTHONPATH", ""),
            },
        )
        if proc.returncode != 0:
            log(f"{mode} FAILED:\n{proc.stderr[-2000:]}")
            sys.exit(1)
        log(proc.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    main()
