"""paddle_tpu.obs — end-to-end observability (ISSUE 9):

- `trace` — per-request timelines (`traceparent` ingestion, phase spans
  that tile the request's latency, bounded LRU timeline store);
- `flight_recorder` — process-global black-box ring of structured fault/
  lifecycle events, dumped atomically on breaker-open / SIGTERM /
  pump crash (postmortem CLI: tools/flight_recorder.py);
- `prom` — shared Prometheus text-exposition plumbing + the
  `pdtpu_train_*` training exporter and opt-in MetricsServer;
- `goodput` (ISSUE 10) — the shared `PhaseLedger` frame bookkeeping and
  the training goodput ledger (phase seconds tile wall clock), live-MFU
  accounting, recompile sentinel, and HBM telemetry / OOM forensics;
- `serving_ledger` (ISSUE 11) — the serving economics ledger (pump
  phase tiling, token efficiency, per-tenant/per-class device-seconds)
  and the SLO burn-rate monitor;
- `compile_observatory` (ISSUE 12) — the process-global registry of
  every jitted executable (signature fingerprints, AOT cost/memory
  analyses, dispatch + device-seconds accounting) and the recompile
  explainer that names the culprit leaf behind every post-warmup
  recompile;
- `flops` — the per-chip peak table (read by `chip_smoke.py`) and the
  analytic FLOPs helpers behind the live MFU gauges;
- `numerics` (ISSUE 13) — the training numerics observatory: in-step
  grad/param/update-ratio telemetry, the culprit-named non-finite blame
  report, and the loss-spike sentinel, plus the shared non-finite
  counting helpers amp/pipeline reuse.

Stdlib-only and import-light: serving and training both depend on this
package, never the other way around.
"""
from .compile_observatory import (CompileObservatory, compile_observatory,
                                  diff_signatures, fingerprint_of,
                                  signature_of)
from .deploy_metrics import DeployMetrics
from .flight_recorder import DUMP_DIR_ENV, FlightRecorder, flight_recorder
from .flops import decode_mfu, peak_flops, train_flops_per_step
from .goodput import (PHASES, GoodputLedger, HBMTelemetry, PhaseLedger,
                      RecompileSentinel, oom_forensics)
from .numerics import (NumericsObservatory, all_finite, bracket_path,
                       current_numerics, nonfinite_count, nonfinite_total,
                       telemetry_groups)
from .prom import MetricsServer, PromBuilder, TrainingMetrics, parse_exposition
from .serving_ledger import (SERVING_LEDGER_PHASES, ServingLedger,
                             SLOBurnMonitor)
from .trace import (LLM_PHASES, SERVING_PHASES, RequestTrace, TimelineStore,
                    ingest_traceparent, new_request_id)

__all__ = [
    "CompileObservatory", "compile_observatory", "diff_signatures",
    "fingerprint_of", "signature_of",
    "DeployMetrics",
    "DUMP_DIR_ENV", "FlightRecorder", "flight_recorder",
    "decode_mfu", "peak_flops", "train_flops_per_step",
    "PHASES", "GoodputLedger", "HBMTelemetry", "PhaseLedger",
    "RecompileSentinel", "oom_forensics",
    "NumericsObservatory", "all_finite", "bracket_path", "current_numerics",
    "nonfinite_count", "nonfinite_total", "telemetry_groups",
    "SERVING_LEDGER_PHASES", "ServingLedger", "SLOBurnMonitor",
    "MetricsServer", "PromBuilder", "TrainingMetrics", "parse_exposition",
    "LLM_PHASES", "SERVING_PHASES", "RequestTrace", "TimelineStore",
    "ingest_traceparent", "new_request_id",
]
