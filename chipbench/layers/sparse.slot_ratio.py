"""sparse.slot_ratio — the layout rule's own quotient, as the sparse fits of
the window saw it: the slots a row-regular step would walk for the table
(the program's ``train.sparse_ell_slots_reckoned`` counter: ``mb x`` the
widest row ``x steps x epochs``, counted by every fit whose pack was asked
for either layout) over the slots the step walked (``train.sparse_slots``).
The pack lays row-regular where it is at most 1.75 (``lib/common.py``
``_ELL_MAX_SLOT_RATIO``): a table of one width reads 1, a ragged one says by
how much it failed the rule.  A program without the counter, or no such fit
in the window, gives nothing."""


def read(ctx, metric):
    reckoned = ctx.counter("train.sparse_ell_slots_reckoned")
    slots = ctx.counter("train.sparse_slots")
    if not reckoned or not slots:
        return None
    return reckoned / slots
