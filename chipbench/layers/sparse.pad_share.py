"""sparse.pad_share — of the entry slots the segment-CSR step walked inside
the window (``train.sparse_slots``: the padded width of a step x steps x
epochs), the share that held no stored entry (``train.sparse_entries``), in
%: what the static shape of a step costs.  A program without the counters, or
no sparse fit in the window, gives nothing."""


def read(ctx, metric):
    slots = ctx.counter("train.sparse_slots")
    if not slots:
        return None
    return 100.0 * (1.0 - ctx.counter("train.sparse_entries") / slots)
