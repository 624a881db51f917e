"""pack_sparse.host_s — seconds of set-up the host spent in the program's
``pack_sparse_minibatches`` (its own ``phase.pack_sparse`` timing: the order
check of the CSR column and the copy of its entries into the segment-CSR
steps).  A sweep packs once, in set-up.  A program that did not pack a sparse
table gives nothing."""


def read(ctx, metric):
    seconds, count = ctx.timing("phase.pack_sparse", ctx.phase(metric))
    return seconds if count else None
