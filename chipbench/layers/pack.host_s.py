"""pack.host_s(.<suffix>) — seconds the host spent in the program's
``pack_minibatches`` (its own ``phase.pack_dense`` timing): the whole of
set-up's where the metric moves ``setup_s`` (a sweep packs once, there), else
per pack inside the window (a mix whose jobs each pack a new table)."""


def read(ctx, metric):
    phase = ctx.phase(metric)
    seconds, count = ctx.timing("phase.pack_dense", phase)
    if not count:
        return None
    return seconds if phase == "setup" else seconds / count
