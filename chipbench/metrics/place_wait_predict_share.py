"""Share of the jobs' waits spent behind a prediction wave: over every
``engine.job`` span in the window (first enqueue to dispatch), its overlap
with the ``predict.wave`` spans, over the sum of their lengths (the
program's recorder)."""
from chipbench.spans import union_length


def read(run):
    sp = getattr(run, "program_spans", None)
    if sp is None:
        return None
    jobs = sp.in_window("engine.job")
    waves = sp.by_layer.get("predict.wave", [])
    total = sum(e - s for s, e in jobs)
    if not waves or total <= 0.0:
        return None
    return 100.0 * sum(union_length(waves, s, e) for s, e in jobs) / total
