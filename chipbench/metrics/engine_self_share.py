"""Share of the window spent in ``EventEngine.run`` itself: its span less
the parts covered by the generator, prediction, decision and coordinator
spans."""
from chipbench.spans import union_length


def read(run):
    sp = run.spans
    (lo, hi), = sp.by_layer["engine"]
    children = [s for layer in ("gen", "predict", "decide", "coord")
                for s in sp.by_layer.get(layer, ())]
    return 100.0 * ((hi - lo) - union_length(children, lo, hi)) / run.window_s
