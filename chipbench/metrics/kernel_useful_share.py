"""Share of the (row, tree) cells the GBDT kernel computed in the window
that are real: n x T over n_pad x T_pad summed over its calls, with the
padded sizes from the program's ``ops.gbdt_padded_shape`` (the sums the
service counts as ``ServiceStats.kernel_cells`` and
``kernel_padded_cells``). None on a program without that helper."""


def read(run):
    from repro.kernels import ops

    padded = getattr(ops, "gbdt_padded_shape", None)
    if padded is None or not run.kernel_calls:
        return None
    cells = sum(n * t for n, _, t, _ in run.kernel_calls)
    computed = 0
    for n, _, t, _ in run.kernel_calls:
        n_pad, t_pad = padded(n, t)
        computed += n_pad * t_pad
    return 100.0 * cells / computed
