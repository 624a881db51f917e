"""place.h2d_enqueue_s — seconds of set-up the host spent handing the slab's
slices to the runtime (the program's ``place.h2d`` span: the ``device_put``
calls of a placement, to the last one's return).  ``device_put`` is
asynchronous and the program adds no wait, so this is the host's side of the
copy and NOT the copy's time: no rate is made of it.  The reassembling program
and its compile lie outside the span."""


def read(ctx, metric):
    seconds, count = ctx.timing("place.h2d", ctx.phase(metric))
    return seconds if count else None
