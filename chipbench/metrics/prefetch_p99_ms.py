"""99th percentile of the durations of the prefetch waves in the window
(``PredictionService.prefetch_tables``), cached or not."""
import numpy as np


def read(run):
    waves = run.spans.by_call.get("predict.prefetch_tables", [])
    if not waves:
        return None
    return float(np.percentile(waves, 99)) * 1e3
