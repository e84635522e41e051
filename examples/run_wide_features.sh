#!/usr/bin/env bash
# Wide (sparse) feature-space GLM: the >100k-column regime of the
# reference's off-heap feature index (util/PalDBIndexMap.scala). Features
# ingest straight to padded-ELL (--sparse) and the power-law head of the
# column distribution is densified onto the MXU (--hot-columns -1, the
# measured-cost-model auto split — ops/sparse.py::to_hybrid).
set -euo pipefail
cd "$(dirname "$0")"
export PYTHONPATH="..${PYTHONPATH:+:$PYTHONPATH}"

python make_wide_data.py

python -m photon_ml_tpu.cli.train \
  --train-input data/wide \
  --validate-input data/wide \
  --output-dir output/wide \
  --task LOGISTIC_REGRESSION \
  --optimizer LBFGS \
  --reg-type L2 \
  --reg-weights 1 \
  --max-iters 60 \
  --sparse --hot-columns -1 \
  --overwrite

echo "wide-features outputs:" && ls output/wide
