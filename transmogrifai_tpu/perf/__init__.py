"""perf/: a learned cost model driving the repo's tuning knobs.

The repo emits a timed profile corpus on every run (journal
`duration_s` stamps, `IngestStats`, serving latency histograms); this
package fits a small per-target predictor on it (`perf/model.py`) and
closes the loop into three consumers:

- `parallel/scheduler.py` orders grid blocks by PREDICTED seconds (true
  LPT) and sizes block widths toward a seconds-per-block target;
- `parallel/bigdata.py` picks upload workers/depth from the predicted
  read-vs-upload balance;
- `serving/batcher.py` derives the bucket ladder from the observed
  request-size distribution + predicted per-bucket latency.

Cold start (empty corpus, or ``TRANSMOGRIFAI_PERF_MODEL=0``): every
consumer reproduces today's heuristics bit-for-bit. Every decision
records its predicted-vs-measured residual (``perf_model_abs_rel_err``
histogram + ``perf_residual`` events), so the model is continuously
scored in production; ``python bench.py costmodel`` reports holdout
MAPE per target and the measured packing improvement.
"""

from transmogrifai_tpu.perf.corpus import (
    CostCorpus, device_generation, get_corpus, harvest_journal, note,
    note_parse, note_serving)
from transmogrifai_tpu.perf.features import (
    block_features, ingest_features, parse_features, serving_features)
from transmogrifai_tpu.perf.model import (
    CostModel, Prediction, choose_upload_plan, fit_corpus, get_model,
    holdout_mape, observe, predict_block_seconds, predict_bucket_seconds,
    predict_drain_seconds, predict_sweep_seconds, refresh, set_model)
from transmogrifai_tpu.perf.params import (
    PerfModelParams, enabled, get_params, params_scope,
    resolved_corpus_dir, set_params, target_block_s)

__all__ = [
    "CostCorpus", "CostModel", "PerfModelParams", "Prediction",
    "block_features", "choose_upload_plan", "device_generation",
    "enabled", "fit_corpus",
    "get_corpus", "get_model", "get_params", "harvest_journal",
    "holdout_mape",
    "ingest_features", "note", "note_parse", "note_serving", "observe",
    "params_scope", "parse_features", "predict_block_seconds",
    "predict_bucket_seconds", "predict_drain_seconds",
    "predict_sweep_seconds", "resolved_corpus_dir", "refresh",
    "serving_features", "set_model", "set_params", "target_block_s",
]
